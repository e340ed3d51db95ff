"""Typed sampler specification — ONE description of a fused-BPT sampling
configuration, shared by every consumer of RRR batches.

``SamplerSpec`` is frozen and hashable (all-primitive fields) so it can key
jit caches, be embedded in ``PoolConfig``, and round-trip through checkpoint
manifests.  The (diffusion × backend) support matrix:

    backend \\ diffusion |  ic  |  lt
    --------------------+------+------
    dense               |  ✓   |  ✓     CSR edge-centric sweep
    tiled               |  ✓   |  ✓     block-sparse tiles, jnp oracle
    kernel              |  ✓   |  ✓     block-sparse tiles, Pallas kernels
                                        (`fused_expand` / `lt_select_expand`)
    data_parallel       |  ✓   |  ✓     shard_map batch blocks over a mesh
    graph_parallel      |  ✓   |  ✓     rows over ``model`` + batches over
                                        ``data`` on a 2-D mesh (frontier
                                        all-gather per level; honors the
                                        kernel leg via REPRO_GP_KERNEL=1)

The RNG contract every backend honors: batch ``b`` under ``master_seed`` is
a pure function of ``(graph, master_seed, b)`` — the same ``(seed, starts)``
derivation everywhere — so supported backends are bit-identical per batch
index and a pool may be built under one backend and extended under another.
"""
from __future__ import annotations

import dataclasses

DIFFUSIONS = ("ic", "lt")
BACKENDS = ("dense", "tiled", "kernel", "data_parallel", "graph_parallel")
FRONTIERS = ("dense", "sparse")

# (diffusion, backend) pairs with an implementation behind them — the
# matrix is complete: LT's per-(vertex, color) live-edge selection has its own
# Pallas kernel (`kernels.lt_select_expand`) mirroring the IC expand kernel.
_SUPPORTED = frozenset(
    (d, b) for d in DIFFUSIONS for b in BACKENDS)


def supported(diffusion: str, backend: str) -> bool:
    """True iff the (diffusion, backend) cell of the matrix is implemented."""
    return (diffusion, backend) in _SUPPORTED


@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Complete description of one traversal-sampling configuration.

    ``max_iters`` is the level cap of the level-synchronous traversal (the
    paper's ``max_levels``).  ``tile_size`` only matters to the tile-layout
    backends (tiled/kernel/graph_parallel); ``mesh_axis`` is the batch axis
    of the mesh backends (``data_parallel`` shards batch blocks over it,
    ``graph_parallel`` its sample axis); ``model_axis`` is the
    ``graph_parallel`` row-partition axis — destination rows shard over it
    and the per-level frontier all-gather runs on it alone.

    ``frontier`` selects the per-level execution mode — ``"dense"`` sweeps
    every edge/tile every level; ``"sparse"`` compacts each level to the
    active source tiles (`core.sparse` — per-level work scales with the
    live frontier instead of E) and, on ``graph_parallel``, additionally
    all-gathers a compacted frontier representation when it fits.  The two
    modes are **bit-identical**; sparse only changes what gets computed,
    never what comes out.  ``frontier_capacity`` tunes the sparse capacity
    buckets (0 = auto ladder): the active-tile compaction buffer size for
    the single-device / data_parallel engines, the per-shard packed-word
    budget of the sparse all-gather for ``graph_parallel``
    (`benchmarks/bench_frontier_profile.py` prints the occupancy histogram
    to set it from).
    """
    diffusion: str = "ic"
    backend: str = "dense"
    num_colors: int = 64
    master_seed: int = 0
    max_iters: int = 64
    sort_starts: bool = False
    tile_size: int = 128
    mesh_axis: str = "data"
    model_axis: str = "model"
    frontier: str = "dense"
    frontier_capacity: int = 0

    def __post_init__(self):
        if self.diffusion not in DIFFUSIONS:
            raise ValueError(f"diffusion {self.diffusion!r} not in "
                             f"{DIFFUSIONS}")
        if self.backend not in BACKENDS:
            raise ValueError(f"backend {self.backend!r} not in {BACKENDS}")
        if not supported(self.diffusion, self.backend):
            raise ValueError(
                f"unsupported combination diffusion={self.diffusion!r} × "
                f"backend={self.backend!r}; supported: "
                f"{sorted(_SUPPORTED)}")
        if self.num_colors < 1 or self.max_iters < 1 or self.tile_size < 1:
            raise ValueError("num_colors / max_iters / tile_size must be ≥ 1")
        if self.frontier not in FRONTIERS:
            raise ValueError(f"frontier {self.frontier!r} not in {FRONTIERS}")
        if self.frontier_capacity < 0:
            raise ValueError("frontier_capacity must be ≥ 0 (0 = auto)")
        if self.backend == "graph_parallel" \
                and self.mesh_axis == self.model_axis:
            raise ValueError(
                "graph_parallel needs DISTINCT axes: mesh_axis (batches) "
                f"and model_axis (graph rows) are both {self.mesh_axis!r}")

    def replace(self, **kw) -> "SamplerSpec":
        return dataclasses.replace(self, **kw)

    # ------------------------------------------------- manifest round-trip
    def to_manifest(self) -> dict:
        """JSON-serializable form for checkpoint manifest ``extra``."""
        return dataclasses.asdict(self)

    @classmethod
    def from_manifest(cls, d: dict) -> "SamplerSpec":
        """Inverse of ``to_manifest`` (unknown keys ignored — forward
        compatible with manifests written by newer specs)."""
        names = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in names})


def resolve_spec(spec: SamplerSpec | None = None, *,
                 num_colors: int | None = None,
                 master_seed: int | None = None) -> SamplerSpec:
    """THE one spec-vs-arguments reconciliation policy, shared by every
    consumer (`PoolConfig`, `run_imm`/`estimate_theta`,
    `rrr.sample_collection`, `SamplingDriver`).

    ``num_colors``/``master_seed`` are ``None`` when the caller did not set
    them explicitly.  An explicit ``spec`` wins over unset arguments, and
    a set argument that disagrees with the spec raises — never a silent
    override.
    """
    if spec is None:
        return SamplerSpec(
            num_colors=64 if num_colors is None else num_colors,
            master_seed=0 if master_seed is None else master_seed)
    for name, mine in (("num_colors", num_colors),
                       ("master_seed", master_seed)):
        theirs = getattr(spec, name)
        if mine is not None and mine != theirs:
            raise ValueError(f"{name}={mine} conflicts with "
                             f"spec.{name}={theirs} — set it in one place")
    return spec
