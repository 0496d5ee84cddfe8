"""Communication/computation overlap policy for sharded stencil updates.

Every sharded stencil update used to serialize on its halo exchange:
``Decomposition.pad_with_halos`` issues ``lax.ppermute`` on boundary
slabs, concatenates the padded block, and only then does the stencil
run — so ICI latency sat directly on the step critical path (visible as
the ``halo`` scope fraction in ``perf_report.md``). The overlapped path
splits each update into an *interior* region (radius-``h`` inset — needs
no remote data) and boundary *shells*, issues the ``ppermute``s first,
computes the interior while the collectives are in flight, then computes
and stitches the shells once halos land. XLA's latency-hiding scheduler
can then genuinely hide the transfer behind the interior work — the
canonical optimization for distributed finite-difference solvers
(Devito's MPI-X "computation/communication overlap", arxiv 2312.13094;
the interior/boundary split of arxiv 2309.04671).

This module is the POLICY side: :func:`enabled` resolves whether a
given mesh takes the overlapped path: per-call/constructor override >
``PYSTELLA_HALO_OVERLAP`` env (``1``/``0``/``auto``) > auto (on for
sharded meshes, i.e. >1 rank on any lattice axis). The setting and the
scheduler flags it depends on are recorded in every report's
environment fingerprint (:func:`pystella_tpu.obs.memory.
flags_fingerprint`).

The MECHANISM lives in
:meth:`~pystella_tpu.DomainDecomposition.overlap_stencil` (XLA-stencil
tier) and :class:`~pystella_tpu.ops.pallas_stencil.OverlapStreamingStencil`
(Pallas tier: the interior is the ring kernel over the raw shard with
its grid inset by one ``h``-row x-block at either end, the two shells
are ``h``-row launches on the slabs and ``2h`` local rows, and their
rows are put into the interior's full-lattice outputs in place, so the
split costs what the single launch costs); when overlap cannot help
(unsharded meshes, blocks thinner than ``3h``, y/z-sharded Pallas
tiles, reduction-emitting kernels) every consumer falls back to the
single launch — the two paths are bit-exact, so the choice is pure
scheduling.
"""

from __future__ import annotations

import logging

from pystella_tpu import config as _config

logger = logging.getLogger(__name__)

__all__ = ["enabled", "env_setting", "MIN_INTERIOR_FACTOR"]

#: a block must span at least ``MIN_INTERIOR_FACTOR * h`` sites along a
#: communicated axis for the interior/shell split to leave a non-empty
#: interior worth hiding the transfer behind (two h-deep shells + at
#: least h interior rows); thinner blocks take the padded path.
MIN_INTERIOR_FACTOR = 3


def env_setting():
    """The raw ``PYSTELLA_HALO_OVERLAP`` setting: ``True``/``False`` for
    an explicit 1/0, ``None`` for unset/auto."""
    val = _config.getenv("PYSTELLA_HALO_OVERLAP").strip().lower()
    if val in ("1", "true", "on", "yes"):
        return True
    if val in ("0", "false", "off", "no"):
        return False
    if val not in ("", "auto"):
        logger.warning("PYSTELLA_HALO_OVERLAP=%r not understood; "
                       "treating as 'auto'", val)
    return None


def enabled(decomp=None, override=None):
    """Should stencil consumers on ``decomp``'s mesh take the overlapped
    halo path? Resolution order: explicit per-call/constructor
    ``override`` > ``PYSTELLA_HALO_OVERLAP`` env > auto (on exactly when
    the mesh shards at least one lattice axis — there is nothing to
    overlap on a single-rank mesh)."""
    if override is not None:
        return bool(override)
    env = env_setting()
    if env is not None:
        return env
    if decomp is None:
        return False
    return any(p > 1 for p in decomp.proc_shape)
