"""From a profiler trace to numbers: the benchmark's own reducer and its
own name table (nothing of ``pystella_tpu.obs.trace`` is used).

Two steps, so that the second can be checked on a recorded trace:

``record(logdir)`` reads the ``.xplane.pb`` the JAX profiler wrote and
keeps, per TPU plane, the events of the ``XLA Ops`` line as ``[name index,
start_ns, duration_ns]`` (a TPU trace names an op by its whole HLO
instruction, so the distinct names go into one table), the ``XLA Modules``
events, and from the host the benchmark's own annotations
(``bench:...``). The record is plain JSON;
``benchmark/selftest/trace_v5e_coupled.json.gz`` is one.

``reduce(record, ...)`` turns a record into the per-layer numbers.
"""

import bisect
import glob
import gzip
import json
import os
import re

from benchmark.spans import PREFIX

#: HLO opcodes that are collectives (also their -start / -done halves)
COLLECTIVE = re.compile(
    r"\b(collective-permute|all-to-all|all-reduce|all-gather|"
    r"reduce-scatter)(-start|-done)?\(")
#: what marks an HLO instruction as a Pallas (Mosaic) kernel
PALLAS_MARK = 'custom_call_target="tpu_custom_call"'
SHORT = re.compile(r"^%([\w\-]+?)(?:\.\d+)? = ")
SHAPE = re.compile(r"\b(f64|f32|bf16|f16|s32|u32|s16|u16|s8|u8|pred)"
                   r"\[([\d,]*)\]")
ITEMSIZE = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
            "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}
NAME_CHARS = 1600


def find_xplane(logdir):
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    return max(hits, key=os.path.getmtime) if hits else None


def record(logdir):
    """The compact record of the newest trace under ``logdir`` (``None``
    when the profiler wrote none)."""
    from jax.profiler import ProfileData

    path = find_xplane(logdir)
    if path is None:
        return None
    data = ProfileData.from_file(path)
    names, index = [], {}
    rec = {"names": names, "devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        name = ev.name[:NAME_CHARS]
                        if name not in index:
                            index[name] = len(names)
                            names.append(name)
                        dev["ops"].append([index[name], int(ev.start_ns),
                                           int(ev.duration_ns)])
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        dev["modules"].append([ev.name, int(ev.start_ns),
                                               int(ev.duration_ns)])
            rec["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        rec["host"].append([ev.name[len(PREFIX):],
                                            int(ev.start_ns),
                                            int(ev.duration_ns)])
    return rec


def save(rec, path):
    with gzip.open(path, "wt") as f:
        json.dump(rec, f, separators=(",", ":"))


def load(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


# -- interval arithmetic ---------------------------------------------------

def union(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, windows):
    """The parts of sorted disjoint ``intervals`` inside ``windows``."""
    out = []
    for w0, w1 in windows:
        for a, b in intervals:
            lo, hi = max(a, w0), min(b, w1)
            if hi > lo:
                out.append([lo, hi])
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """``intervals`` minus the union ``holes`` (both sorted, disjoint)."""
    out = []
    for a, b in intervals:
        cur = a
        for h0, h1 in holes:
            if h1 <= cur or h0 >= b:
                continue
            if h0 > cur:
                out.append([cur, h0])
            cur = max(cur, h1)
        if cur < b:
            out.append([cur, b])
    return out


def inside(t, windows):
    """Is instant ``t`` inside one of the sorted disjoint ``windows``."""
    i = bisect.bisect_right(windows, [t, float("inf")]) - 1
    return i >= 0 and windows[i][0] <= t <= windows[i][1]


# -- what an HLO instruction says about itself -----------------------------

def short_name(text):
    m = SHORT.match(text)
    return m.group(1) if m else text.split(" ")[0][:40]


def shapes(text):
    return [(dt, [int(n) for n in dims.split(",") if n])
            for dt, dims in SHAPE.findall(text)]


def nbytes(dtype, dims):
    n = ITEMSIZE[dtype]
    for d in dims:
        n *= d
    return n


def slab_stencil_bytes(text, local_shape):
    """Bytes one event of a y-slab stencil kernel must move, from the
    shapes its own HLO instruction prints (copied from
    ``FusedScalarStepper._stencil_bytes``: every output written once,
    every lattice input read once over the slab; halo windows, scalars'
    reads and re-reads are not counted, so the kernel moves at least
    this). The slab's y extent is the outputs'; a lattice operand is one
    whose last three dimensions are the local lattice, padded or not."""
    head, _, tail = text.partition(" custom-call(")
    outs = shapes(head)
    ins = shapes(tail.split("), custom_call_target")[0])
    X, Y, Z = local_shape
    lattice_outs = [(dt, d) for dt, d in outs if len(d) >= 3 and d[-1] == Z]
    if not lattice_outs:
        return None
    by = lattice_outs[0][1][-2]
    total = sum(nbytes(dt, d) for dt, d in outs)
    for dt, d in ins:
        if len(d) >= 3 and d[-1] == Z and d[-2] >= Y and d[-3] >= X:
            lead = 1
            for n in d[:-3]:
                lead *= n
            total += ITEMSIZE[dt] * lead * X * by * Z
    return total


def kernel_file(text, kernels):
    """The kernel file an instruction belongs to: the first (by name)
    whose ``match`` strings all appear in it."""
    for name, spec in sorted(kernels.items()):
        if all(m in text for m in spec["match"]):
            return name
    return None


def signature(text):
    """A kernel event's kind as far as its instruction shows it: how many
    arrays it writes and reads."""
    head, _, tail = text.partition(" custom-call(")
    outs = [d for _, d in shapes(head) if len(d) >= 3]
    ins = [d for _, d in shapes(tail.split("), custom_call_target")[0])
           if len(d) >= 3]
    sums = sum(1 for _, d in shapes(head) if len(d) == 2)
    return f"{len(ins)}in/{len(outs)}out" + (f"+{sums}sums" if sums else "")


# -- the reduction ---------------------------------------------------------

def host_windows(rec, name):
    return sorted([s, s + d] for n, s, d in rec["host"] if n == name)


def reduce(rec, kernels, local_shape, peak_gbps, steps_traced):
    """Per-layer numbers of one traced cycle (two blocks and an output).

    Kernel time is the time of the Pallas events inside blocks; the
    roofline takes its bytes from those same events
    (``slab_stencil_bytes``, for a kernel that has a file), its time from
    them, and the peak from ``peaks.json``. A Pallas event inside a block with no kernel file
    leaves the roofline out and is named in ``notes``."""
    blocks = host_windows(rec, "unit:block")
    outputs = host_windows(rec, "unit:output")
    step_spans = host_windows(rec, "step_call")
    names = rec["names"]
    info = []
    for text in names:
        pallas = PALLAS_MARK in text
        kfile = kernel_file(text, kernels) if pallas else None
        info.append({
            "short": short_name(text), "pallas": pallas, "file": kfile,
            "collective": bool(COLLECTIVE.search(text)),
            "bytes": (slab_stencil_bytes(text, local_shape)
                      if kfile else None),
            "sig": signature(text) if pallas else None})
    out, notes = {}, []
    ndev = max(len(rec["devices"]), 1)
    busy = out_busy = kern_ns = prog_ns = prog_kern_ns = 0.0
    coll_ns = expo_ns = roof_ns = roof_bytes = 0.0
    per_kernel, unknown, groups, gaps = {}, {}, {}, []
    for plane, dev in sorted(rec["devices"].items()):
        ops = dev["ops"]
        mods = sorted(dev["modules"], key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        all_busy = union([[s, s + d] for _, s, d in ops])
        in_blocks = clip(all_busy, blocks)
        busy += length(in_blocks)
        out_busy += length(clip(all_busy, outputs))
        for g0, g1 in subtract([list(b) for b in blocks], in_blocks):
            gaps.append((g1 - g0, g0, g1))
        compute = union([[s, s + d] for i, s, d in ops
                         if not info[i]["collective"]])
        for i, s, d in ops:
            meta = info[i]
            j = bisect.bisect_right(mod_starts, s) - 1
            mod = "?"
            if j >= 0 and s <= mods[j][1] + mods[j][2]:
                mod = re.sub(r"\(\d+\)$", "", mods[j][0])
            key = mod + "/" + meta["short"]
            groups[key] = groups.get(key, 0) + d
            mid = s + d / 2
            if not inside(mid, blocks):
                continue
            in_step = inside(mid, step_spans)
            if in_step:
                prog_ns += d
            if meta["collective"]:
                coll_ns += d
                expo_ns += length(subtract([[s, s + d]], compute))
            if not meta["pallas"]:
                continue
            kern_ns += d
            if in_step:
                prog_kern_ns += d
            if meta["file"] is None or meta["bytes"] is None:
                unknown[meta["short"] + " " + meta["sig"]] = unknown.get(
                    meta["short"] + " " + meta["sig"], 0) + 1
                continue
            k = per_kernel.setdefault(
                (meta["file"], meta["sig"]), [0, 0.0, 0.0])
            k[0] += 1
            k[1] += d
            k[2] += meta["bytes"]
            roof_ns += d
            roof_bytes += meta["bytes"]
    window_ns = length(blocks)
    if not window_ns or not busy:
        return {}, ["no traced block, or no device op inside one"], {}
    steps = float(steps_traced)
    out["kernel_ms_per_step"] = kern_ns / ndev / steps / 1e6
    if step_spans:
        out["step_program_other_ms_per_step"] = (
            (prog_ns - prog_kern_ns) / ndev / steps / 1e6)
    out["device_idle_share"] = 100.0 * (1.0 - busy / ndev / window_ns)
    if outputs:
        out["output_idle_share"] = 100.0 * (
            1.0 - out_busy / ndev / length(outputs))
    if ndev > 1:
        out["collective_ms_per_step"] = coll_ns / ndev / steps / 1e6
        out["collective_exposed_ms_per_step"] = expo_ns / ndev / steps / 1e6
    for (kfile, sig), (count, ns, nb) in sorted(per_kernel.items()):
        notes.append(
            f"kernel {kfile} {sig}: {count} events, {nb / count / 1e6:.1f} "
            f"MB each, {ns / 1e6 / ndev:.3f} ms per chip, {nb / ns:.1f} GB/s")
    if unknown:
        notes.append(f"Pallas ops inside blocks with no kernel file, the "
                     f"roofline is left out: {unknown}")
    elif roof_ns:
        out["stencil_kernel_roofline"] = 100.0 * (
            roof_bytes / (peak_gbps * 1e9)) / (roof_ns / 1e9)
    out["busy_s"] = busy / ndev / 1e9
    out["window_s"] = window_ns / 1e9
    breakdown = {
        "device_ops": [[k, v / ndev / 1e9] for k, v in sorted(
            groups.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": idle_gaps(rec, gaps, ndev)}
    return out, notes, breakdown


def idle_gaps(rec, gaps, ndev):
    """The device's idle time inside the traced blocks by what the host
    was doing (the innermost benchmark span over the gap's middle), then
    the longest single gaps."""
    host = sorted(([s, s + d, n] for n, s, d in rec["host"]
                   if not n.startswith("unit:")), key=lambda r: r[1] - r[0])
    by_span = {}

    def owner(mid):
        for s, e, n in host:  # shortest first: the innermost
            if s <= mid <= e:
                return n
        return "host_other"

    for dur, g0, g1 in gaps:
        name = owner((g0 + g1) / 2)
        by_span[name] = by_span.get(name, 0) + dur
    rows = [[k, v / ndev / 1e9] for k, v in sorted(
        by_span.items(), key=lambda kv: -kv[1])[:5]]
    rows += [["longest:" + owner((g0 + g1) / 2), dur / 1e9]
             for dur, g0, g1 in sorted(gaps, reverse=True)[:5]]
    return rows[:10]
