"""Pluggable zone-scan backend registry (the executor's dispatch layer).

Every Phase-1 implementation (growth-zone candidate expansion) is published
here as a :class:`BackendSpec` carrying the scan callable plus capability
metadata the executor needs to drive it correctly:

* ``jittable``  — whether the scan is JAX-traceable (can live inside
  ``jax.jit`` / ``shard_map``).  The pure-NumPy oracle backend is host-side
  and runs outside the jit boundary.
* ``grade``     — "reference" (vectorized jnp, exact), "accelerator"
  (Pallas TPU kernel, exact, fast), or "oracle" (brute-force host walk,
  the ground-truth semantics tests cross-check against).
* ``block_defaults`` — kernel tile sizes (e.g. Pallas ``c_blk``/``e_blk``)
  owned by the backend, not by call sites.
* ``default_zone_chunk`` / ``max_recommended_e_cap`` — scheduling hints.
* ``mem_model`` / ``default_merge_cap`` — memory hints for the capacity
  planner (:mod:`repro.core.planner`): ``mem_model(e_cap, l_max)`` is the
  backend's per-zone scan footprint in bytes (the Pallas kernel pads the
  edge axis up to block multiples, so its zones cost more than the
  reference model says), and ``default_merge_cap`` bounds the hierarchical
  aggregation carry when the executor is not given an explicit cap.

Backends self-describe; the executor, the distributed mining step, and the
CLI all resolve scans through :func:`get_backend` instead of hand-rolled
``if backend == ...`` chains.  Registration is lazy: the loader imports the
implementation on first use, so importing this module never pulls in Pallas.
"""

from __future__ import annotations

import dataclasses
from typing import Callable

__all__ = [
    "BackendSpec",
    "available_backends",
    "get_backend",
    "register_backend",
]


@dataclasses.dataclass
class BackendSpec:
    """One registered zone-scan implementation plus its capabilities.

    ``scan`` has the reference signature
    ``scan(u, v, t, valid, *, delta, l_max) -> ZoneResult`` over a
    ``[Z, E]`` zone batch (arrays are jnp for jittable backends, numpy
    for host backends).
    """

    name: str
    loader: Callable[[], Callable]
    jittable: bool = True
    grade: str = "reference"
    description: str = ""
    block_defaults: dict | None = None
    default_zone_chunk: int | None = None
    max_recommended_e_cap: int | None = None
    mem_model: Callable[[int, int], int] | None = None
    default_merge_cap: int | None = None
    fused_loader: Callable[[], Callable] | None = None
    #: Whether ``scan`` (and ``fused_scan``, if any) accept a ``with_ts``
    #: keyword returning per-step absorption timestamps — the input the
    #: executor's config-lattice co-mining fold needs to derive smaller
    #: configs' counts from one dominating sweep.
    supports_comine: bool = False
    _scan: Callable | None = dataclasses.field(
        default=None, repr=False, compare=False)
    _fused_scan: Callable | None = dataclasses.field(
        default=None, repr=False, compare=False)

    @property
    def scan(self) -> Callable:
        """Resolve (and cache) the scan callable."""
        if self._scan is None:
            self._scan = self.loader()
        return self._scan

    @property
    def supports_fused(self) -> bool:
        """Whether this backend publishes a flat single-launch scan."""
        return self.fused_loader is not None

    @property
    def fused_scan(self) -> Callable:
        """Resolve (and cache) the fused flat-stream scan callable.

        Signature: ``fused_scan(u, v, t, valid, zone_id, lo, hi, *, delta,
        l_max, blk) -> (code int32[S, L], length int32[S])`` over a
        concatenated :class:`repro.core.tzp.FusedZoneLayout` slot stream,
        where ``lo``/``hi`` are the layout's per-candidate-block sweep
        bounds (host-planned compaction).
        """
        if self.fused_loader is None:
            raise ValueError(
                f"backend {self.name!r} has no fused single-launch scan "
                f"(fused paths need a bucket-native kernel; use the "
                f"per-bucket layout path instead)")
        if self._fused_scan is None:
            self._fused_scan = self.fused_loader()
        return self._fused_scan


_REGISTRY: dict[str, BackendSpec] = {}


def register_backend(
    name: str,
    loader: Callable[[], Callable],
    *,
    jittable: bool = True,
    grade: str = "reference",
    description: str = "",
    block_defaults: dict | None = None,
    default_zone_chunk: int | None = None,
    max_recommended_e_cap: int | None = None,
    mem_model: Callable[[int, int], int] | None = None,
    default_merge_cap: int | None = None,
    fused_loader: Callable[[], Callable] | None = None,
    supports_comine: bool = False,
    overwrite: bool = False,
) -> BackendSpec:
    """Publish a zone-scan backend under ``name``.

    ``loader`` is a zero-arg callable returning the scan function; it runs
    at most once, on first :func:`get_backend` resolution.
    ``fused_loader`` (optional) resolves the backend's single-launch flat
    scan over a concatenated ragged layout — see ``BackendSpec.fused_scan``.
    """
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         f"(pass overwrite=True to replace)")
    spec = BackendSpec(
        name=name, loader=loader, jittable=jittable, grade=grade,
        description=description, block_defaults=block_defaults,
        default_zone_chunk=default_zone_chunk,
        max_recommended_e_cap=max_recommended_e_cap,
        mem_model=mem_model, default_merge_cap=default_merge_cap,
        fused_loader=fused_loader, supports_comine=supports_comine,
    )
    _REGISTRY[name] = spec
    return spec


def get_backend(name: str) -> BackendSpec:
    """Look up a backend; error lists what is available."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        ) from None


def available_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# Built-in backends.
# ---------------------------------------------------------------------------


def _load_ref():
    from repro.core import expansion

    return expansion.scan_zones


# Pallas zone-scan tile sizes (candidates x edges per VMEM block).  Defined
# here — importable without pulling in Pallas — and consumed by
# kernels/zone_scan/ops.py as its call defaults, so registry metadata and
# kernel defaults cannot drift.
PALLAS_BLOCK_DEFAULTS = {"c_blk": 512, "e_blk": 256}

#: Candidate-block width of the fused single-launch flat kernel.  Matches
#: ``c_blk`` so a fused candidate block does the same lane-width work as a
#: dense candidate block — but sweeps only its own zones' flat span instead
#: of a whole padded bucket.
FUSED_BLK_DEFAULT = 512


def _load_pallas():
    from repro.kernels.zone_scan import ops as zone_ops

    return zone_ops.scan_zones


def _load_pallas_fused():
    from repro.kernels.zone_scan import ops as zone_ops

    return zone_ops.scan_flat


def _load_xla_fused():
    from repro.kernels.zone_scan import xla as zone_xla

    return zone_xla.scan_flat_xla


def _load_numpy():
    from repro.core import scan_numpy

    return scan_numpy.scan_zones


def _ref_mem_model(e_cap: int, l_max: int) -> int:
    from repro.core import planner

    return planner.ref_zone_bytes(e_cap, l_max)


def _pallas_mem_model(e_cap: int, l_max: int) -> int:
    from repro.core import planner

    return planner.pallas_zone_bytes(e_cap, l_max, **PALLAS_BLOCK_DEFAULTS)


register_backend(
    "ref", _load_ref,
    jittable=True, grade="reference",
    description=("vectorized jnp expansion, its loop bounded by the "
                 "Lemma-4.1 horizon (exact, any device)"),
    mem_model=_ref_mem_model,
    supports_comine=True,
)

register_backend(
    "pallas", _load_pallas,
    jittable=True, grade="accelerator",
    description="Pallas TPU kernel with live-window block skipping",
    block_defaults=PALLAS_BLOCK_DEFAULTS,
    mem_model=_pallas_mem_model,
    fused_loader=_load_pallas_fused,
    supports_comine=True,
)

register_backend(
    "xla", _load_ref,
    jittable=True, grade="reference",
    description=("compiled XLA lowering: reference scan plus a pure "
                 "lax fused flat scan (fast on CPU, no interpreter)"),
    mem_model=_ref_mem_model,
    fused_loader=_load_xla_fused,
    supports_comine=True,
)

register_backend(
    "numpy", _load_numpy,
    jittable=False, grade="oracle",
    description="pure-NumPy brute-force walk (ground truth, small inputs)",
    max_recommended_e_cap=4096,
    mem_model=_ref_mem_model,
    default_merge_cap=4096,
    supports_comine=True,
)
