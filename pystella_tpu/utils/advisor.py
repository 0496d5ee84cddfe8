"""Shape advisor: which meshes fit a lattice, and which kernel tier
each subsystem takes there.

The framework requires per-axis divisibility of the grid by the process
mesh (a documented design decision vs the reference's uneven shards,
/root/reference/pystella/decomp.py:322-337 — XLA sharding wants even
blocks), and its fastest kernel tiers have alignment requirements of
their own (``Z % 128`` lanes for compiled streaming stencils, ``Y % 8``
sublanes for their blocking, pencil-FFT divisibility). Those constraints
live where they are enforced; this module turns them into ONE actionable
report: given ``(grid_shape, n_devices)``, every feasible mesh plus the
tier each subsystem selects on it (fused/streaming/resident/halo;
pencil/partial/replicate), so a user picks shapes by reading one table
instead of hitting the constraints one ValueError at a time
(VERDICT r4 #9).

Use :func:`advise_shapes` programmatically, or the CLI::

    python -m pystella_tpu.utils.advisor 512 512 512 -n 64
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

__all__ = ["advise_shapes", "MeshAdvice", "ShapeReport"]


def _factorizations(n):
    """All ordered (px, py, pz) with px*py*pz == n."""
    out = []
    for px in range(1, n + 1):
        if n % px:
            continue
        rem = n // px
        for py in range(1, rem + 1):
            if rem % py:
                continue
            out.append((px, py, rem // py))
    return out


def _streaming_feasible(n_win, local, h, itemsize, n_extra, n_out):
    """Mirror of the compiled StreamingStencil gates: lane-aligned z,
    and a blocking that fits the VMEM budget (choose_blocks)."""
    from pystella_tpu.ops.pallas_stencil import LANE, choose_blocks
    if local[2] % LANE:
        return False, f"Z={local[2]} % {LANE} != 0"
    try:
        bx, by = choose_blocks(n_win, local, h, itemsize, n_extra, n_out)
        return True, f"blocking ({bx},{by})"
    except ValueError as e:
        return False, str(e).split(";")[0]


def _resident_feasible(n_win, local, h, itemsize, n_extra, n_out):
    """Mirror of the ResidentStencil VMEM gate (whole lattice + tap
    temporaries in VMEM)."""
    budget = 64 * 2**20
    nio = n_win + n_extra + n_out
    need = (nio + (6 * h + 2) * n_win) * int(np.prod(local)) * itemsize
    return need <= budget, f"~{need / 2**20:.0f} MB VMEM"


@dataclass
class MeshAdvice:
    """Per-mesh feasibility and tier selection."""
    proc_shape: tuple
    local_shape: tuple
    tiers: dict = field(default_factory=dict)
    notes: list = field(default_factory=list)

    @property
    def fused_ok(self):
        return not self.tiers.get("fused stepper", "").startswith("generic")

    def row(self):
        p = "x".join(map(str, self.proc_shape))
        loc = "x".join(map(str, self.local_shape))
        return [p, loc] + [self.tiers.get(k, "-") for k in TIER_KEYS]


TIER_KEYS = ("fused stepper", "pair fusion", "coupled pair",
             "FD operators", "distributed FFT", "multigrid depth",
             "HBM/device")


@dataclass
class ShapeReport:
    grid_shape: tuple
    n_devices: int
    meshes: list
    infeasible: list  # [(proc_shape, reason)]

    def best(self):
        """The recommended mesh (first after sorting)."""
        return self.meshes[0] if self.meshes else None

    def format(self):
        lines = [f"grid {self.grid_shape} on {self.n_devices} device(s):"]
        if not self.meshes:
            lines.append("  NO feasible mesh — every factorization fails "
                         "per-axis divisibility:")
            for p, why in self.infeasible[:8]:
                lines.append(f"    {p}: {why}")
            return "\n".join(lines)
        hdr = ["mesh", "local"] + list(TIER_KEYS)
        rows = [m.row() for m in self.meshes]
        widths = [max(len(str(r[i])) for r in [hdr] + rows)
                  for i in range(len(hdr))]
        lines.append("  " + "  ".join(h.ljust(w)
                                      for h, w in zip(hdr, widths)))
        for r in rows:
            lines.append("  " + "  ".join(str(c).ljust(w)
                                          for c, w in zip(r, widths)))
        for m in self.meshes:
            for note in m.notes:
                lines.append(f"  note [{'x'.join(map(str, m.proc_shape))}]:"
                             f" {note}")
        if self.infeasible:
            lines.append(f"  ({len(self.infeasible)} factorization(s) "
                         "fail divisibility — not shown)")
        return "\n".join(lines)


def advise_shapes(grid_shape, n_devices=1, halo_shape=2,
                  dtype=np.float32, nscalars=2,
                  gravitational_waves=False):
    """Report the feasible process meshes for ``grid_shape`` over
    ``n_devices`` and the kernel tier each subsystem takes on each.

    :arg grid_shape: global lattice ``(Nx, Ny, Nz)``.
    :arg n_devices: total device count to factor into a mesh.
    :arg halo_shape: stencil radius ``h``.
    :arg dtype: lattice dtype (sets the VMEM feasibility math).
    :arg nscalars: scalar field count ``F`` (window widths scale with it).
    :arg gravitational_waves: include the 6-component tensor sector in
        the fused-kernel window accounting.

    Returns a :class:`ShapeReport`; ``report.format()`` is the printable
    table, ``report.best()`` the recommended mesh. The tier logic
    mirrors the gates where they are enforced: ``Z % 128`` lane tiles
    and ``choose_blocks`` VMEM fits for compiled streaming stencils
    (ops/pallas_stencil.py), the ResidentStencil whole-lattice VMEM
    budget, the three DFT schemes (fourier/dft.py), and per-axis
    divisibility (parallel/decomp.py rank_shape).
    """
    grid_shape = tuple(int(n) for n in grid_shape)
    itemsize = np.dtype(dtype).itemsize
    h = int(halo_shape)
    F = int(nscalars)
    H = 6 if gravitational_waves else 0
    from pystella_tpu.ops.pallas_stencil import LANE

    meshes, infeasible = [], []
    for proc in _factorizations(int(n_devices)):
        bad = [f"axis {i}: {n} % {p} != 0"
               for i, (n, p) in enumerate(zip(grid_shape, proc)) if n % p]
        if bad:
            infeasible.append((proc, "; ".join(bad)))
            continue
        local = tuple(n // p for n, p in zip(grid_shape, proc))
        m = MeshAdvice(proc, local)
        px, py, pz = proc
        ndev = int(n_devices)

        # fused steppers: z must stay whole per device (VMEM lane axis)
        if pz > 1:
            m.tiers["fused stepper"] = "generic (z-sharded)"
            m.tiers["pair fusion"] = "-"
            m.tiers["coupled pair"] = "-"
        else:
            # single-stage kernel: windows F (+H), extras 3F (+3H),
            # outs 4F (+4H)
            nw, ne, no = F + H, 3 * (F + H), 4 * (F + H)
            ok, why = _streaming_feasible(nw, local, h, itemsize, ne, no)
            if ok:
                m.tiers["fused stepper"] = "streaming"
            elif px == 1 and py == 1 and _resident_feasible(
                    nw, local, h, itemsize, ne, no)[0]:
                m.tiers["fused stepper"] = "resident"
            else:
                m.tiers["fused stepper"] = "generic (XLA halo)"
                m.notes.append(f"fused streaming infeasible: {why}")
            # stage-pair kernel: windows 3F(+3H), extras F(+H)
            ok_p, _ = _streaming_feasible(
                3 * (F + H), local, h, itemsize, F + H, no)
            res_p = (px == 1 and py == 1 and _resident_feasible(
                3 * (F + H), local, h, itemsize, F + H, no)[0])
            m.tiers["pair fusion"] = ("yes" if (ok_p or res_p)
                                      else "no (VMEM)")
            # deferred-drag coupled pair: windows 4F(+4H), no extras
            ok_c, _ = _streaming_feasible(
                4 * (F + H), local, h, itemsize, 0, no)
            res_c = (px == 1 and py == 1 and _resident_feasible(
                4 * (F + H), local, h, itemsize, 0, no)[0])
            m.tiers["coupled pair"] = ("yes" if (ok_c or res_c)
                                       else "no (VMEM)")

        # FiniteDifferencer: one-component window, grad+lap outputs
        if pz > 1:
            m.tiers["FD operators"] = "halo (z-sharded)"
        else:
            ok, why = _streaming_feasible(1, local, h, itemsize, 0, 4)
            if ok:
                m.tiers["FD operators"] = "pallas"
            elif (px == 1 and py == 1
                  and _resident_feasible(1, local, h, itemsize, 0, 4)[0]):
                m.tiers["FD operators"] = "resident"
            else:
                m.tiers["FD operators"] = "halo"

        # FFT scheme selection: the shard_map pencil tier
        # (fourier/pencil.py, make_dft's auto choice) when x/y divide
        # the total device count, else the DFT fallback chain
        # (fourier/dft.py partial/replicate)
        if ndev == 1:
            m.tiers["distributed FFT"] = "local"
        elif (grid_shape[0] % ndev == 0 and grid_shape[1] % ndev == 0):
            m.tiers["distributed FFT"] = "pencil-a2a"
        elif (pz == 1 and grid_shape[0] % px == 0
                and grid_shape[1] % py == 0):
            m.tiers["distributed FFT"] = "partial"
            m.notes.append(
                "partial FFT tier only: grid x/y divisible by the "
                f"TOTAL device count ({ndev}) would enable the fully "
                "distributed pencil tier (no transient replication)")
        else:
            m.tiers["distributed FFT"] = "replicate!"
            # complex HALF-spectrum itemsize (r2c): 2x the real dtype,
            # min complex64, over (Nx, Ny, Nz//2+1)
            kshape = (grid_shape[0], grid_shape[1],
                      grid_shape[2] // 2 + 1)
            nbytes = int(np.prod(kshape)) * max(2 * itemsize, 8)
            m.notes.append(
                "no distributed FFT scheme: transforms would replicate "
                f"~{nbytes / 2**30:.1f} GiB per device (raises above "
                "the replicate limit) — prefer a grid whose x/y axes "
                f"divide the device count ({ndev}), which takes the "
                "pencil tier instead")

        # multigrid: depth while every LOCAL axis stays even and >= 4
        depth = 0
        loc = list(local)
        while all(n % 2 == 0 and n // 2 >= 4 for n in loc):
            loc = [n // 2 for n in loc]
            depth += 1
        m.tiers["multigrid depth"] = str(depth)

        # peak HBM per device for the hot loop: one state + one carry
        # (4 arrays per field component with per-stage donation —
        # doc/performance.md "Memory"); bfloat16 carries halve the
        # carry half (carry_dtype=jnp.bfloat16 on the fused steppers)
        sites = int(np.prod(local))
        narr = 2 * (F + H)  # state: (y, dy) per component
        gb = narr * sites * itemsize * 2 / 1e9  # + same-size carry
        gb_bf16 = narr * sites * itemsize * 1.5 / 1e9
        tag = f"~{gb:.1f} GB"
        if gb > 16:
            tag += (f" (>16! bf16 carries: ~{gb_bf16:.1f} GB)"
                    if gb_bf16 <= 16 else " (>16 GB: shard wider)")
            m.notes.append(
                f"f32-carry peak ~{gb:.1f} GB/device exceeds a 16 GB "
                f"chip; carry_dtype=jnp.bfloat16 gives ~{gb_bf16:.1f} "
                "GB" + ("" if gb_bf16 <= 16 else
                        " — still over; use a larger mesh"))
        m.tiers["HBM/device"] = tag

        if local[2] % LANE and pz == 1:
            m.notes.append(
                f"local Z={local[2]} is not lane-aligned ({LANE}): "
                "compiled streaming kernels unavailable; resident/halo "
                "tiers apply")

        meshes.append(m)

    # preference: fused streaming > resident > generic; then pencil FFT;
    # then minimal halo surface (communication)
    def key(m):
        fused_rank = {"streaming": 0, "resident": 1}.get(
            m.tiers["fused stepper"], 2)
        fft_rank = {"local": 0, "pencil-a2a": 0, "partial": 1}.get(
            m.tiers["distributed FFT"], 2)
        px, py, pz = m.proc_shape
        X, Y, Z = m.local_shape
        surface = ((Y * Z if px > 1 else 0) + (X * Z if py > 1 else 0)
                   + (X * Y if pz > 1 else 0))
        return (fused_rank, fft_rank, surface)

    meshes.sort(key=key)
    return ShapeReport(grid_shape, int(n_devices), meshes, infeasible)


def main(argv=None):
    from argparse import ArgumentParser
    parser = ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("grid_shape", type=int, nargs=3,
                        metavar=("Nx", "Ny", "Nz"))
    parser.add_argument("-n", "--n-devices", type=int, default=1)
    parser.add_argument("--halo-shape", type=int, default=2)
    parser.add_argument("--dtype", type=np.dtype, default=np.float32)
    parser.add_argument("--nscalars", type=int, default=2)
    parser.add_argument("--gravitational-waves", "-gws",
                        action="store_true")
    p = parser.parse_args(argv)
    report = advise_shapes(p.grid_shape, p.n_devices, p.halo_shape,
                           p.dtype, p.nscalars, p.gravitational_waves)
    print(report.format())


if __name__ == "__main__":
    main()
