"""The energy's Laplacian handed to the next stage program
(``pystella_tpu/handoff.py``; ``SpectralCollocator.lap``; the generic
``Stepper._dispatch_stage``).

The loop is the example's (and ``benchmark/drivers/spectral_stage_loop``'s):
``derivs.lap(state["f"])`` for the energy between two stages, then
``stepper(s, carry, ...)`` whose right-hand side takes ``derivs.lap`` of
that very array. The control is the same loop whose energy takes the
Laplacian of a **copy** of ``f``: identity fails, every stage builds its
transforms as it always did, and no switch is needed to say so.
"""

import ast
import gc
import os

import numpy as np
import pytest

import common  # noqa: F401  (side effect: enables x64)

import jax
import jax.numpy as jnp

import pystella_tpu as ps
from pystella_tpu import handoff
from pystella_tpu.obs import metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GRID = (32, 32, 32)
COUNTERS = ("stage_dispatches", "stage_laplacians_handed_in")


def counts():
    snap = metrics.registry().snapshot()
    return tuple(int(snap.get(name, 0)) for name in COUNTERS)


def counted(before):
    return tuple(now - was for now, was in zip(counts(), before))


class Loop:
    """The example's ``--halo-shape 0`` pieces at 32**3: transform with
    the inverse by matrix products, collocator, two coupled fields,
    ``LowStorageRK54(full_rhs)``, the energy reduction and the
    background's ODE on the host."""

    def __init__(self, dtype, proc_shape=(1, 1, 1), derivs=None, **stepper_kw):
        ndev = int(np.prod(proc_shape))
        self.dtype = np.dtype(dtype)
        self.decomp = ps.DomainDecomposition(
            proc_shape, devices=jax.devices()[:ndev])
        self.lattice = ps.Lattice(GRID, (5.0,) * 3, dtype=dtype)
        kw = dict(grid_shape=GRID, dtype=dtype, real_inverse="matmul")
        self.fft = (ps.make_dft(self.decomp, **kw) if ndev > 1
                    else ps.DFT(self.decomp, **kw))
        self.derivs = derivs(self) if derivs else ps.SpectralCollocator(
            self.fft, self.lattice.dk)

        def potential(f):
            return (f[0]**2 / 2 + 3 * f[1]**2 / 2
                    + 0.7 * f[0]**2 * f[1]**2)

        self.sector = ps.ScalarSector(2, potential=potential)
        self.sector_rhs = ps.compile_rhs_dict(self.sector.rhs_dict)
        self.dt = dtype(0.1 * min(self.lattice.dx))
        self.stepper = ps.LowStorageRK54(self.full_rhs, dt=self.dt,
                                         **stepper_kw)
        self.reduce_energy = ps.Reduction(
            self.decomp, self.sector, callback=ps.get_rho_and_p,
            grid_size=float(np.prod(GRID)))

    def full_rhs(self, state, t, a, hubble):
        return self.sector_rhs(state, t, lap_f=self.derivs.lap(state["f"]),
                               a=a, hubble=hubble)

    def seeded(self, seed=7):
        rng = np.random.default_rng(seed)
        return {k: self.decomp.shard(
            (0.3 * rng.standard_normal((2,) + GRID)).astype(self.dtype))
            for k in ("f", "dfdt")}

    def compute_energy(self, state, a, copy=False):
        f = state["f"]
        # the control: equal values in another buffer
        lap_f = self.derivs.lap(f + 0 if copy else f)
        return self.reduce_energy(f=f, dfdt=state["dfdt"], lap_f=lap_f,
                                  a=np.float64(a))

    def run(self, nsteps, copy=False):
        stepper = self.stepper
        state = self.seeded()
        energy = self.compute_energy(state, 1.0, copy)
        expand = ps.Expansion(energy["total"], ps.LowStorageRK54, mpl=1.0)
        t = self.dtype.type(0)
        for _ in range(nsteps):
            carry = state
            for s in range(stepper.num_stages):
                carry = stepper(s, carry, t, a=np.float64(expand.a),
                                hubble=np.float64(expand.hubble))
                expand.step(s, energy["total"], energy["pressure"], self.dt)
                now = (carry if s == stepper.num_stages - 1
                       else stepper.current(carry))
                energy = self.compute_energy(now, expand.a, copy)
            state = carry
            t += self.dt
        return jax.device_get(state), float(expand.a)


def worst(got, want):
    return max(float(np.max(np.abs(got[k] - want[k]))
                     / np.max(np.abs(want[k]))) for k in want)


def assert_same_loop_with_and_without(loop, tol, nsteps=2):
    stages = nsteps * loop.stepper.num_stages
    before = counts()
    got, a_got = loop.run(nsteps)
    assert counted(before) == (stages, stages)
    before = counts()
    want, a_want = loop.run(nsteps, copy=True)
    assert counted(before) == (stages, 0)
    assert worst(got, want) < tol
    assert abs(a_got - a_want) <= tol * abs(a_want)
    assert np.all(np.isfinite(got["f"])) and worst(got, loop.seeded()) > 1e-3


# -- (a), (e): the loop, every stage a hit --------------------------------

@pytest.mark.parametrize("dtype,tol", [(np.float64, 1e-13),
                                       (np.float32, 1e-6)])
def test_every_stage_takes_the_energys_laplacian(dtype, tol):
    """Ten of ten stage programs of two steps take the Laplacian the
    energy has just taken, the first stage of the first step included;
    the state they reach is the one ten transform pairs more reach."""
    with jax.enable_x64(dtype is np.float64):
        assert_same_loop_with_and_without(Loop(dtype), tol)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="a (2, 2, 1) mesh")
@pytest.mark.parametrize("dtype,tol", [(np.float64, 1e-13),
                                       (np.float32, 1e-6)])
def test_every_stage_takes_it_on_a_mesh(dtype, tol):
    """The same on four devices through ``make_dft``'s ``PencilFFT``:
    what is handed in is each device's shard of the Laplacian."""
    with jax.enable_x64(dtype is np.float64):
        loop = Loop(dtype, proc_shape=(2, 2, 1))
        assert loop.fft.scheme == "pencil-a2a"
        assert_same_loop_with_and_without(loop, tol)


def test_other_tableaus_and_later_leaves_hit_too():
    """A classical tableau's carry holds the stage input as ``q[1]``:
    the leaf is found wherever it lies."""
    loop = Loop(np.float64)
    loop.stepper = ps.RungeKutta4(loop.full_rhs, dt=loop.dt)
    assert_same_loop_with_and_without(loop, 1e-13, nsteps=1)


# -- (b): misses give today's program and today's answer -------------------

class CountingTransforms:
    """How many times a collocator's Laplacian program was asked for:
    dispatched eagerly, or built into a caller's trace."""

    def __init__(self, derivs, monkeypatch):
        self.n = 0
        inner = derivs._lap

        def lap(fx):
            self.n += 1
            return inner(fx)

        monkeypatch.setattr(derivs, "_lap", lap)


def todays_stage0(loop, state, args):
    """Stage 0 through five arguments, on a stepper of its own."""
    fresh = ps.LowStorageRK54(loop.full_rhs, dt=loop.dt)
    fresh._ensure_stage_jits()
    loop.derivs._last_lap.forget()
    return fresh._jit_stage0(state, 0.0, loop.dt, args)


def same_bits(a, b):
    return all(np.array_equal(x, y) for x, y in zip(
        jax.tree_util.tree_leaves(jax.device_get(a)),
        jax.tree_util.tree_leaves(jax.device_get(b))))


ARGS = {"a": np.float64(1.0), "hubble": np.float64(0.2)}


@pytest.mark.parametrize("case", ["another_buffer", "nothing_before",
                                  "another_call_between",
                                  "another_collocator"])
def test_a_miss_is_todays_program(case, monkeypatch):
    loop = Loop(np.float64)
    state = loop.seeded()
    want = todays_stage0(loop, state, ARGS)
    built = CountingTransforms(loop.derivs, monkeypatch)
    if case == "another_buffer":
        loop.derivs.lap(state["f"] + 0)
    elif case == "another_call_between":
        loop.derivs.lap(state["f"])
        loop.derivs.grad(state["f"])       # forgets, before it allocates
        assert loop.derivs._last_lap._value is None
    elif case == "another_collocator":
        ps.SpectralCollocator(loop.fft, [2 * k for k in loop.lattice.dk]
                              ).lap(state["f"])
        built.n = 0
    before, was_built = counts(), built.n
    got = loop.stepper(0, state, 0.0, **ARGS)
    if case == "another_collocator":
        # handed in at dispatch, and nobody in the trace asks for it: the
        # right-hand side's own collocator builds its transforms
        assert counted(before) == (1, 1)
    else:
        assert counted(before) == (1, 0)
    assert built.n == was_built + 1
    assert same_bits(got, want)


def test_a_leaf_the_rhs_touches_first_is_recomputed(monkeypatch):
    """Handed in at dispatch (the leaf is the remembered array), but the
    right-hand side takes ``lap`` of another tracer: the transforms are
    built as ever, and the answer is the one they give."""
    loop = Loop(np.float64)
    built = CountingTransforms(loop.derivs, monkeypatch)

    def rescaling_rhs(state, t, a, hubble):
        return loop.sector_rhs(
            state, t, lap_f=loop.derivs.lap(2 * state["f"]) / 2,
            a=a, hubble=hubble)

    state = loop.seeded()
    with_hit = ps.LowStorageRK54(rescaling_rhs, dt=loop.dt)
    without = ps.LowStorageRK54(rescaling_rhs, dt=loop.dt)
    want = without(0, state, 0.0, **ARGS)
    assert built.n == 1                    # in the stage's trace
    loop.derivs.lap(state["f"])
    before = counts()
    got = with_hit(0, state, 0.0, **ARGS)
    assert counted(before) == (1, 1)
    assert built.n == 3                    # eagerly, and in the trace again
    assert same_bits(got, want)


def test_a_hit_builds_no_transform(monkeypatch):
    loop = Loop(np.float64)
    built = CountingTransforms(loop.derivs, monkeypatch)
    state = loop.seeded()
    loop.derivs.lap(state["f"])
    assert built.n == 1
    carry = loop.stepper(0, state, 0.0, **ARGS)
    assert built.n == 1
    text = str(loop.stepper._jit_stage0.trace(
        state, 0.0, loop.dt, ARGS,
        handoff.HandedIn(state["f"], 1, loop.derivs._last_lap.serial)).jaxpr)
    assert "fft" not in text and "dot_general" not in text
    assert built.n == 1
    # and the pair is gone the moment a program has taken it
    assert loop.derivs._last_lap._value is None
    before = counts()
    loop.stepper(1, carry, 0.0, **ARGS)
    assert counted(before) == (1, 0) and built.n == 2


# -- (c): donation, and who holds the Laplacian ------------------------------

def test_donating_stepper_keeps_a_held_laplacian_and_misses_a_deleted_key():
    loop = Loop(np.float64, donate=True)
    state = loop.seeded()
    want = todays_stage0(loop, jax.tree_util.tree_map(jnp.copy, state), ARGS)
    lap_f = loop.derivs.lap(state["f"])
    kept = np.asarray(lap_f)
    before = counts()
    carry = loop.stepper(0, state, 0.0, **ARGS)
    # somebody holds it: not handed in, today's program, and it survives
    assert counted(before) == (1, 0)
    assert state["f"].is_deleted()                 # the state was donated
    assert not lap_f.is_deleted()
    assert np.array_equal(np.asarray(lap_f), kept)
    assert same_bits(carry, want)
    assert loop.derivs._last_lap._value is None    # and the pair is gone
    # the energy of a state that is then donated elsewhere (the seeded
    # draw's ``add`` program): the remembered key is a deleted array
    state = loop.seeded()
    loop.derivs.lap(state["f"])
    moved = jax.jit(lambda s: jax.tree_util.tree_map(lambda x: x + 1, s),
                    donate_argnums=0)(state)
    assert state["f"].is_deleted()
    assert handoff.take(state) is None and handoff.take(moved) is None
    before = counts()
    carry = loop.stepper(0, moved, 0.0, **ARGS)
    assert counted(before) == (1, 0)
    jax.block_until_ready(carry)


@pytest.mark.parametrize("donate", [False, True])
def test_a_laplacian_nobody_else_holds_is_consumed(donate, recwarn):
    """Where the dispatch holds the only reference to the Laplacian (the
    loop's: ``compute_energy``'s local is gone) the stage program takes it
    as a donated argument, whatever the stepper donates of its own; the
    answer is the bits of the program that transforms for itself. What
    the reference count cannot see (a ``weakref``) finds it deleted."""
    import weakref
    loop = Loop(np.float64, donate=donate)
    state = loop.seeded()
    want = todays_stage0(loop, jax.tree_util.tree_map(jnp.copy, state), ARGS)
    watcher = weakref.ref(loop.derivs.lap(state["f"]))  # nobody keeps it
    assert watcher() is loop.derivs._last_lap._value
    before = counts()
    got = loop.stepper(0, state, 0.0, **ARGS)
    assert counted(before) == (1, 1)
    assert watcher() is None or watcher().is_deleted()
    assert same_bits(got, want)
    assert not [w for w in recwarn.list if "donated" in str(w.message)]
    fresh = loop.seeded()
    lowered = loop.stepper._jit_stage0.trace(
        fresh, 0.0, loop.dt, ARGS, handoff.HandedIn(
            fresh["f"], 1, loop.derivs._last_lap.serial)).lower().as_text()
    assert "jax.buffer_donor" in lowered or "tf.aliasing_output" in lowered


def test_only_ours_is_what_the_running_interpreter_counts_for_one_name():
    """``take`` tells "nobody else holds it" by the reference count of a
    value held by one local name: pinned here on the interpreter that
    runs the suite, so that one which counts otherwise fails this and
    not the hit share."""
    import sys
    value = object()
    assert sys.getrefcount(value) == handoff._SOLE
    other = value
    assert sys.getrefcount(value) == handoff._SOLE + 1
    del other
    box = [value]
    assert sys.getrefcount(value) == handoff._SOLE + 1
    # and through ``take`` itself: a name, a container, nobody
    loop = Loop(np.float64)
    state = loop.seeded()
    held = loop.derivs.lap(state["f"])
    assert handoff.take(state) is None and not held.is_deleted()
    box[0] = loop.derivs.lap(state["f"])
    del held
    assert handoff.take(state) is None
    loop.derivs.lap(state["f"])
    handed, name = handoff.take(state)
    assert name == "SpectralCollocator.lap" and handed.leaf == 1
    assert handoff.take(state) is None             # taken once


def test_two_threads_tracing_over_one_collocator_each_see_their_own(
        monkeypatch):
    """What is on offer while a program is traced is the tracing
    context's own: two stage programs over one collocator, traced at the
    same time, each take the Laplacian they were handed."""
    import threading
    loop = Loop(np.float64)
    states = [loop.seeded(seed) for seed in (3, 4)]
    wants = [todays_stage0(loop, state, ARGS) for state in states]
    both_tracing = threading.Barrier(2, timeout=60)

    def rhs(state, t, a, hubble):
        both_tracing.wait()
        return loop.full_rhs(state, t, a, hubble)

    handed = []
    for state in states:
        loop.derivs.lap(state["f"])
        handed.append(handoff.take(state)[0])
    built = CountingTransforms(loop.derivs, monkeypatch)
    gots, errors = [None, None], []

    def trace_and_run(i):
        try:
            stepper = ps.LowStorageRK54(rhs, dt=loop.dt)
            stepper._ensure_stage_jits()
            gots[i] = stepper._jit_stage0(states[i], 0.0, loop.dt, ARGS,
                                          handed[i])
        except Exception as exc:           # noqa: BLE001 (shown below)
            errors.append(exc)

    threads = [threading.Thread(target=trace_and_run, args=(i,))
               for i in range(2)]
    for thread in threads:
        thread.start()
    for thread in threads:
        thread.join()
    assert not errors, errors
    assert built.n == 0                    # neither built a transform
    assert all(same_bits(got, want) for got, want in zip(gots, wants))
    assert handoff._on_offer.get() is None


def test_a_laplacian_its_owner_deleted_is_a_miss():
    loop = Loop(np.float64)
    state = loop.seeded()
    loop.derivs.lap(state["f"]).delete()
    before = counts()
    jax.block_until_ready(loop.stepper(0, state, 0.0, **ARGS))
    assert counted(before) == (1, 0)


# -- (d): a stencil registers nothing --------------------------------------

def test_a_stencil_stepper_compiles_one_program_a_stage_index():
    loop = Loop(np.float64, derivs=lambda lp: ps.FiniteDifferencer(
        lp.decomp, 2, lp.lattice.dx))
    before = counts()
    loop.run(2)
    assert counted(before) == (10, 0)
    stepper = loop.stepper
    assert stepper._jit_stage0._cache_size() == 1
    assert stepper._jit_stage._cache_size() == 4
    state = loop.seeded()
    traced = stepper._jit_stage.trace(1, (state, state), 0.0, loop.dt, ARGS)
    # y and k (f, dfdt each), t, dt, a, hubble: today's arguments
    assert len(traced.jaxpr.in_avals) == 8
    assert len(stepper._jit_stage0.trace(
        state, 0.0, loop.dt, ARGS).jaxpr.in_avals) == 6


def test_the_spectral_steppers_programs_are_two_a_stage_index():
    loop = Loop(np.float64)
    loop.run(1)
    loop.run(1, copy=True)
    state = loop.seeded()
    held = loop.derivs.lap(state["f"])             # a miss: adds no third
    loop.stepper(0, state, np.float64(0), **ARGS)
    assert not held.is_deleted()
    assert loop.stepper._jit_stage0._cache_size() == 2
    assert loop.stepper._jit_stage._cache_size() == 8
    state = loop.seeded()
    serial = loop.derivs._last_lap.serial
    with_lap = loop.stepper._jit_stage.trace(
        1, (state, state), 0.0, loop.dt, ARGS,
        handoff.HandedIn(state["f"], 1, serial))
    assert len(with_lap.jaxpr.in_avals) == 9


# -- (f): the event and the counters ---------------------------------------

def test_one_event_a_stepper_says_which_leaf_and_whose():
    seen = []
    log = ps.obs.get_log()
    tap = log.subscribe(lambda rec: seen.append(rec["data"])
                        if rec["kind"] == "laplacian_handed_in" else None)
    try:
        loop = Loop(np.float32)
        before = counts()
        loop.run(2)
        assert counted(before) == (10, 10)
        second = ps.LowStorageRK54(loop.full_rhs, dt=loop.dt)
        state = loop.seeded()
        carry = second(0, state, 0.0, **ARGS)          # a miss: no event
        loop.derivs.lap(second.current(carry)["f"])
        second(1, carry, 0.0, **ARGS)
    finally:
        log.unsubscribe(tap)
    assert "laplacian_handed_in" in ps.obs.events.registered_event_kinds()
    assert seen == [
        {"stepper": "LowStorageRK54", "stage": 0, "leaf": "['f']",
         "producer": "SpectralCollocator.lap",
         "shape": [2, *GRID], "dtype": "float32"},
        {"stepper": "LowStorageRK54", "stage": 1, "leaf": "[0]['f']",
         "producer": "SpectralCollocator.lap",
         "shape": [2, *GRID], "dtype": "float32"}]


# -- how long the pair lives ------------------------------------------------

@pytest.mark.parametrize("call", ["lap", "grad", "grad_lap", "pdx", "pdy",
                                  "pdz", "divergence"])
def test_every_eager_call_starts_by_forgetting(call):
    loop = Loop(np.float64)
    f = loop.seeded()["f"]
    memo = loop.derivs._last_lap
    loop.derivs.lap(f)
    assert memo._key() is f and memo._value is not None
    other = jnp.stack([f[0]] * 3) if call == "divergence" else f + 1
    out = getattr(loop.derivs, call)(other)
    if call == "lap":
        assert memo._key() is other and memo._value is out
    else:
        assert memo._key is None and memo._value is None
    # under a caller's trace nothing is forgotten and nothing remembered
    loop.derivs.lap(f)
    jax.jit(getattr(loop.derivs, call))(other)
    assert memo._key() is f


def test_the_pair_goes_with_its_array_and_with_its_collocator():
    loop = Loop(np.float64)
    memo = loop.derivs._last_lap
    f = loop.seeded()["f"]
    loop.derivs.lap(f)
    assert memo._value is not None
    del f
    assert memo._key is None and memo._value is None
    serial = memo.serial
    assert handoff._producers.get(serial) is memo
    del loop, memo
    gc.collect()
    assert serial not in handoff._producers


def test_a_host_array_is_not_remembered():
    loop = Loop(np.float64)
    loop.derivs.lap(np.zeros((2,) + GRID))
    assert loop.derivs._last_lap._key is None


# -- who imports whom --------------------------------------------------------

def _imported(path):
    found = set()
    for node in ast.walk(ast.parse(open(os.path.join(ROOT, path)).read())):
        if isinstance(node, ast.Import):
            found.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            found.update(f"{node.module}.{a.name}" for a in node.names)
    return {name for name in found if name.startswith("pystella_tpu")}


def test_the_stepper_and_the_transforms_do_not_import_each_other():
    assert not any(".fourier" in name
                   for name in _imported("pystella_tpu/step.py"))
    fourier = os.path.join(ROOT, "pystella_tpu", "fourier")
    for name in sorted(os.listdir(fourier)):
        if name.endswith(".py"):
            assert not any(
                m.startswith("pystella_tpu.step")
                for m in _imported(f"pystella_tpu/fourier/{name}")), name
    assert _imported("pystella_tpu/handoff.py") == set()
