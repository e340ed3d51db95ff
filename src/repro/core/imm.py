"""IMM influence maximization (Tang, Shi, Xiao 2015) on fused-BPT samples.

Pipeline (paper §2): sample θ RRR sets by fused reverse BPTs, then greedy
max-k-cover over the collection; the cover fraction × n estimates σ(S), and
the martingale bound on θ guarantees (1 − 1/e − ε) approximation.

Seed selection is matmul-shaped on TPU: the uncovered-color marginal gains
are popcount reductions over the columnar bitmask (the coverage kernel), not
atomic list walks — no GPU-style RRR linked lists anywhere.

The greedy inner loop is a single jit-compiled ``lax.fori_loop`` program
(``greedy_extend``): argmax selection and active-mask update stay on device,
with no per-iteration host round-trip.  The same program serves offline
``run_imm`` and the online query engine (`repro.serve.influence`), which
resumes it from arbitrary active masks for marginal-gain-with-exclusion
queries.

Sampling is pluggable through the *sketch pool* protocol: any object with
``num_colors``, ``master_seed`` and ``ensure(num_batches) -> list[RRRBatch]``
(e.g. ``serve.influence.sketch_store.SketchStore``) can back
``estimate_theta`` / ``run_imm``, making offline IMM just one client of a
long-lived sampled-sketch asset.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitmask, rrr
from repro.graph import csr
from repro.kernels import ops


# --------------------------------------------------------------- θ bound
def _log_comb(n: int, k: int) -> float:
    return (math.lgamma(n + 1) - math.lgamma(k + 1) - math.lgamma(n - k + 1))


def _adjusted_ell(n: int, ell: float) -> float:
    return ell * (1 + math.log(2) / math.log(n))


def _lam_star_coeff(n: int, k: int, ell_adj: float) -> float:
    """λ*(ε) = coeff / ε² (Tang et al. Thm 1); ``ell_adj`` pre-adjusted."""
    alpha = math.sqrt(ell_adj * math.log(n) + math.log(2))
    beta = math.sqrt((1 - 1 / math.e)
                     * (_log_comb(n, k) + ell_adj * math.log(n) + math.log(2)))
    return 2 * n * ((1 - 1 / math.e) * alpha + beta) ** 2


def theta_bound(n: int, k: int, eps: float, ell: float = 1.0) -> int:
    """IMM λ*/LB worst-case sample count with LB = 1 (Tang et al. Thm 1).

    The driver uses the iterative LB estimation (``estimate_theta``); this
    closed form is the hard ceiling.
    """
    return int(math.ceil(
        _lam_star_coeff(n, k, _adjusted_ell(n, ell)) / eps ** 2))


def eps_bound_for_theta(n: int, k: int, theta: int, ell: float = 1.0,
                        opt_lb: float = 1.0) -> float:
    """Coverage-error bound a pool of ``theta`` RRR samples certifies.

    Exact inverse of the ``estimate_theta`` sample-count bound
    (θ = ⌈λ*(ε)/LB⌉ with λ* ∝ 1/ε²): the smallest ε whose required θ the
    pool already meets.  ``opt_lb`` is a lower bound on OPT (e.g. the
    greedy σ̂ from a top-k query, which the serving tier's autoscaler
    feeds in); the default 1 is the worst case.  Monotone in θ, so a
    controller can grow/shrink a pool against a target ε without
    re-running the sampling phase.
    """
    theta = max(int(theta), 1)
    return math.sqrt(_lam_star_coeff(n, k, _adjusted_ell(n, ell))
                     / (theta * max(opt_lb, 1.0)))


def estimate_theta(g: csr.Graph, k: int, eps: float, ell: float = 1.0,
                   num_colors: int | None = None,
                   master_seed: int | None = None,
                   max_batches_per_phase: int = 64,
                   g_rev: csr.Graph | None = None,
                   pool=None, spec=None, mesh=None,
                   sampler=None) -> tuple[int, list]:
    """IMM sampling phase: iterative-halving lower bound on OPT → θ.

    Returns (θ, batches generated so far) — generated batches are *reused*
    by the selection phase (IMM's trick to avoid resampling).

    ``g_rev``: prebuilt transpose(g); handed to the sampler so one reversal
    serves both the halving phase and the selection top-up.
    ``pool``: optional sketch pool (see module docstring); when given, the
    pool owns sampling and this function never builds a sampler itself.
    ``spec``/``mesh``: `repro.sampling.SamplerSpec` + mesh for the pool-less
    path (``sampling.resolve_spec`` policy: explicit num_colors/master_seed
    that disagree with the spec raise); ``sampler``: prebuilt
    `repro.sampling.Sampler` (overrides spec).
    """
    from repro import sampling

    spec = sampling.resolve_spec(spec, num_colors=num_colors,
                                 master_seed=master_seed)
    num_colors, master_seed = spec.num_colors, spec.master_seed
    n = g.num_vertices
    ell = _adjusted_ell(n, ell)
    eps_prime = math.sqrt(2) * eps
    lam_prime = ((2 + 2 * eps_prime / 3)
                 * (_log_comb(n, k) + ell * math.log(n)
                    + math.log(math.log2(max(n, 4))))
                 * n / eps_prime ** 2)
    if pool is None and sampler is None:
        sampler = sampling.make_sampler(g, spec, mesh=mesh, g_rev=g_rev)
    batches: list[rrr.RRRBatch] = []

    def grow(want: int) -> list[rrr.RRRBatch]:
        if pool is not None:
            return _pool_take(pool, want)
        if len(batches) < want:
            batches.extend(
                sampler.sample_many(range(len(batches), want)))
        return batches

    lb = 1.0
    for i in range(1, max(int(math.log2(n)), 1)):
        x = n / (2 ** i)
        theta_i = int(math.ceil(lam_prime / x))
        want = min(-(-theta_i // num_colors), max_batches_per_phase)
        cur = grow(want)
        vis = (pool.visited_stack()[:len(cur)] if pool is not None
               else rrr.stack_visited(cur))
        seeds, cov = greedy_max_cover(vis, k, num_colors)
        if n * cov >= (1 + eps_prime) * x:
            lb = n * cov / (1 + eps_prime)
            break
    lam_star = _lam_star_coeff(n, k, ell) / eps ** 2
    return int(math.ceil(lam_star / lb)), (batches if pool is None
                                           else pool.ensure(0))


def _pool_take(pool, want: int) -> list:
    """Exactly ``want`` batches from a sketch pool, as the sample prefix.

    Slicing keeps ``theta_cap`` meaningful against a pre-populated serving
    pool, and raising (rather than silently under-sampling) preserves the
    IMM θ bound when the pool's budget can't supply the batches.
    """
    got = pool.ensure(want)
    if len(got) < want:
        raise ValueError(
            f"sketch pool capacity {len(got)} < {want} batches required by "
            "IMM sampling — raise the pool's max_batches / memory budget, "
            "or lower θ (larger eps, smaller theta_cap)")
    return got[:want]


# ------------------------------------------------------ greedy max-k-cover
def _count_fn(use_kernel: bool):
    """(B, V, W) visited × (B, W) active → (B, V) marginal-gain counts."""
    if use_kernel:
        return ops.cover_counts_batched
    return jax.vmap(lambda vis, act: jnp.sum(
        bitmask.popcount(vis & act[None, :]), -1).astype(jnp.int32))


def greedy_extend_program(visited, active, k: int, use_kernel: bool,
                          all_reduce=None, embed_counts=None, fetch_row=None,
                          final_reduce=None):
    """k rounds of greedy selection as one on-device ``lax.fori_loop``.

    Each round computes all-vertex marginal gains with the coverage kernel,
    argmaxes on device, and strips the winner's colors from the active mask —
    no host synchronization until the caller fetches the result.

    ``all_reduce`` merges per-shard partial reductions when the batch dim is
    sharded (pass ``partial(lax.psum, axis_name=...)`` inside a shard_map;
    identity on one device).  Because the argmax runs on the *merged* counts
    — replicated after the collective — every shard selects the same seed
    with no second collective, and integer summation makes the sharded
    result bit-identical to the single-device one.

    The remaining hooks extend the same program to a pool whose VERTEX
    rows are additionally sharded over a model axis (`ShardedSketchStore`
    row sharding — each shard's ``visited`` is (B_loc, V/M, W)):

    * ``embed_counts`` places a shard's (V_loc,) local counts at its row
      offset in the global (Vp,) vector BEFORE ``all_reduce`` (which then
      psums over data AND model — disjoint offsets make the sum exact and
      the merged counts replicated, so the argmax stays collective-free);
    * ``fetch_row`` maps the selected GLOBAL vertex to its (B_loc, W)
      visited row (owning shard contributes, others zero, one psum over
      model) — the default is the local ``dynamic_index_in_dim``;
    * ``final_reduce`` merges the uncovered popcount — over the data axis
      ONLY when rows are sharded (``active`` is replicated across model
      shards; reusing ``all_reduce`` would overcount M×).  Defaults to
      ``all_reduce``.

    This is a trace-time program, not a jitted function: single-device
    callers go through ``greedy_extend``; the distributed query engine
    (`repro.serve.distributed.engine`) stages it inside a shard_map.
    """
    count = _count_fn(use_kernel)
    merge = all_reduce if all_reduce is not None else (lambda x: x)
    embed = embed_counts if embed_counts is not None else (lambda x: x)
    if fetch_row is None:
        def fetch_row(sel):
            return jax.lax.dynamic_index_in_dim(visited, sel, axis=1,
                                                keepdims=False)   # (B, W)
    final = final_reduce if final_reduce is not None else merge

    def body(i, carry):
        seeds, act = carry
        counts = merge(embed(count(visited, act).sum(0)))       # (Vp,)
        sel = jnp.argmax(counts).astype(jnp.int32)
        seeds = seeds.at[i].set(sel)
        return seeds, act & ~fetch_row(sel)

    seeds0 = jnp.zeros((k,), jnp.int32)
    seeds, active = jax.lax.fori_loop(0, k, body, (seeds0, active))
    uncovered = final(jnp.sum(bitmask.popcount(active)).astype(jnp.int32))
    return seeds, active, uncovered


@functools.partial(jax.jit, static_argnames=("k", "use_kernel"))
def _greedy_extend_jit(visited, active, k: int, use_kernel: bool):
    return greedy_extend_program(visited, active, k, use_kernel)


def initial_active(num_batches: int, num_colors: int) -> jnp.ndarray:
    """(B, W) all-colors-uncovered mask (tail bits past num_colors zeroed)."""
    w = bitmask.num_words(num_colors)
    return jnp.broadcast_to(
        jnp.asarray(bitmask.color_tail_mask(num_colors)), (num_batches, w))


def greedy_extend(visited: jnp.ndarray, active: jnp.ndarray, k: int,
                  use_kernel: bool = True):
    """Extend a partial cover by ``k`` greedy picks from ``active``.

    Returns (seeds (k,) int32 device array, new active (B, W), uncovered
    color count int32 scalar).  This is the shared incremental kernel: pass
    ``initial_active(...)`` for offline selection, or an exclusion-filtered
    mask for online marginal-gain queries.
    """
    return _greedy_extend_jit(visited, active, k, use_kernel)


def greedy_max_cover(visited: jnp.ndarray, k: int, num_colors: int,
                     use_kernel: bool = True):
    """Greedy max-k-cover over a (B, V, W) RRR collection.

    Returns (seeds (k,) int32, covered fraction float).  Thin host wrapper
    over ``greedy_extend`` — one device program, two fetches.
    """
    b, v, w = visited.shape
    theta = b * num_colors
    seeds, _, uncovered = greedy_extend(
        visited, initial_active(b, num_colors), k, use_kernel)
    return np.asarray(seeds), (theta - int(uncovered)) / theta


def greedy_max_cover_ref(visited: jnp.ndarray, k: int, num_colors: int,
                         use_kernel: bool = True):
    """Reference host-loop greedy (pre-refactor semantics) for equivalence
    tests: per-iteration host argmax, same tie-breaking as the jit path."""
    b, v, w = visited.shape
    theta = b * num_colors
    active = np.asarray(initial_active(b, num_colors)).copy()
    count = _count_fn(use_kernel)
    seeds = []
    for _ in range(k):
        counts = count(visited, jnp.asarray(active)).sum(0)     # (V,)
        sel = int(jnp.argmax(counts))
        seeds.append(sel)
        active &= ~np.asarray(visited[:, sel, :])
    covered = theta - int(np.unpackbits(active.view(np.uint8)).sum())
    return np.asarray(seeds, np.int32), covered / theta


def coverage_of(visited: jnp.ndarray, seeds, num_colors: int) -> float:
    """Fraction of RRR sets hit by ``seeds`` (σ(S) ≈ n × this)."""
    b, v, w = visited.shape
    active = initial_active(b, num_colors)
    for s in np.asarray(seeds):
        active = active & ~visited[:, int(s), :]
    theta = b * num_colors
    return (theta - int(jnp.sum(bitmask.popcount(active)))) / theta


# --------------------------------------------------------------- end-to-end
@dataclasses.dataclass(frozen=True)
class IMMResult:
    seeds: np.ndarray
    sigma_estimate: float       # expected influence of the seed set
    theta: int
    coverage: float
    num_batches: int
    fused_edge_visits: int
    unfused_edge_visits: int


def run_imm(g: csr.Graph, k: int, eps: float = 0.3, *, ell: float = 1.0,
            num_colors: int | None = None, master_seed: int | None = None,
            theta_cap: int | None = 100_000, pool=None,
            spec=None, mesh=None, **sample_kw) -> IMMResult:
    """Full IMM: θ estimation → top-up sampling → greedy selection.

    ``pool``: optional sketch pool (module docstring); batches come from and
    stay in the pool, so a serving process can reuse them for online queries.
    Because batch ``b`` is a pure function of ``(graph, master_seed, b)``,
    routing through a *fresh* (never-refreshed) pool with the same
    ``master_seed``/``num_colors`` reproduces the pool-less result exactly;
    selection always uses the first ``⌈θ/colors⌉`` pool slots, so a larger
    pre-populated pool still respects ``theta_cap``.  A pool whose capacity
    cannot supply θ raises rather than silently weakening the bound.

    ``spec``: `repro.sampling.SamplerSpec` choosing diffusion/backend for
    the pool-less path (``sampling.resolve_spec`` policy: explicit
    num_colors/master_seed that disagree with the spec raise); ``mesh``
    backs the ``data_parallel`` backend.  Legacy ``sample_batch`` kwargs
    are converted with a DeprecationWarning.
    """
    from repro import sampling

    explicit_spec = spec is not None
    spec = sampling.resolve_spec(spec, sample_kw, num_colors=num_colors,
                                 master_seed=master_seed)
    num_colors, master_seed = spec.num_colors, spec.master_seed
    if pool is not None:
        if explicit_spec and getattr(pool, "spec", None) is not None \
                and pool.spec.diffusion != spec.diffusion:
            raise ValueError(f"pool diffusion {pool.spec.diffusion!r} != "
                             f"requested {spec.diffusion!r}")
        if pool.num_colors != num_colors:
            raise ValueError(f"pool colors {pool.num_colors} != {num_colors}")
    sampler = None
    if pool is None:
        sampler = sampling.make_sampler(g, spec, mesh=mesh)
    theta, batches = estimate_theta(g, k, eps, ell, spec=spec,
                                    pool=pool, sampler=sampler)
    if theta_cap:
        theta = min(theta, theta_cap)
    want = -(-theta // num_colors)
    if pool is not None:
        batches = _pool_take(pool, want)
        visited = pool.visited_stack()[:want]
    else:
        if len(batches) < want:
            batches.extend(sampler.sample_many(range(len(batches), want)))
        # Selection uses exactly ⌈θ/colors⌉ batches even when the halving
        # phase oversampled — mirrors the pool path's [:want] slice, so
        # pool-routed and pool-less runs agree for every diffusion.
        batches = batches[:want]
        visited = rrr.stack_visited(batches)
    seeds, cov = greedy_max_cover(visited, k, num_colors)
    return IMMResult(
        seeds=seeds, sigma_estimate=cov * g.num_vertices,
        theta=len(batches) * num_colors, coverage=cov,
        num_batches=len(batches),
        # Skip the -1 "not instrumented" sentinels (tiled/kernel/LT/
        # data_parallel batches) so sums never go negative.
        fused_edge_visits=sum(b.fused_edge_visits for b in batches
                              if b.fused_edge_visits >= 0),
        unfused_edge_visits=sum(b.unfused_edge_visits for b in batches
                                if b.unfused_edge_visits >= 0))


def simulate_influence(g: csr.Graph, seeds, num_trials: int = 512,
                       master_seed: int = 77) -> float:
    """σ(S) by forward IC: one color per trial, frontier starts at all of S.

    Under IC, activations from multiple seeds in one realization are exactly
    a BFS from the seed *set* on the realized subgraph — so a single-color
    traversal seeded at every s ∈ S is the correct per-trial sample. Trials
    ride in parallel as colors (distinct counters ⇒ independent subgraphs).
    """
    n = g.num_vertices
    colors = min(num_trials, 256)
    total, trials_done = 0, 0
    while trials_done < num_trials:
        c = min(colors, num_trials - trials_done)
        fr = bitmask.make_mask(n, c)
        for s in np.asarray(seeds):
            fr = bitmask.set_color(fr, jnp.full((c,), int(s), jnp.int32),
                                   jnp.arange(c, dtype=jnp.int32))
        res = _run_from_frontier(g, fr, c,
                                 jnp.uint32(master_seed + trials_done))
        total += int(jnp.sum(bitmask.popcount(res)))
        trials_done += c
    return total / num_trials


def _run_from_frontier(g: csr.Graph, frontier, num_colors: int, seed,
                       max_levels: int = 64):
    """Fused traversal from an arbitrary initial frontier; returns visited."""
    from repro.core import traversal as trav

    visited = jnp.zeros_like(frontier)
    view = trav.dst_view(g)

    def cond(carry):
        fr, _, lvl = carry
        return jnp.logical_and(bitmask.any_set(fr), lvl < max_levels)

    def body(carry):
        fr, vis, lvl = carry
        nf, nv, _ = trav._expand(view, fr, vis, lvl, seed)
        return nf, nv, lvl + 1

    fr, vis, _ = jax.lax.while_loop(cond, body,
                                    (frontier, visited, jnp.int32(0)))
    return vis | fr
