"""Unified sampling facade: one typed traversal spec over every
(diffusion × backend) combination.

    from repro import sampling
    from repro.launch.mesh import make_mesh

    spec    = sampling.SamplerSpec(diffusion="ic", backend="data_parallel",
                                   num_colors=64, master_seed=3)
    sampler = sampling.make_sampler(graph, spec, mesh=mesh)
    batch   = sampler.sample(0)                  # one rrr.RRRBatch
    stack   = sampler.sample_stacked(range(16))  # (16, V, W), mesh-sharded

    # graphs bigger than one device: rows over "model", batches over "data"
    gp = sampling.make_sampler(
        graph, spec.replace(backend="graph_parallel"),
        mesh=make_mesh((4, 2), ("data", "model")))

Every pool consumer (``core.rrr.sample_collection``, ``core.imm.run_imm``,
``serve.influence.SketchStore``, ``serve.distributed.ShardedSketchStore``,
``core.driver.SamplingDriver``) routes RRR sampling through here; the
per-batch ``rrr.sample_batch`` reference is private to this package (CI
grep guard).  The cross-backend contract: a given ``(master_seed,
batch_index)`` yields bit-identical visited masks on every backend that
supports the diffusion.
"""
from repro.sampling.sampler import Sampler, make_sampler
from repro.sampling.spec import (BACKENDS, DIFFUSIONS, FRONTIERS,
                                 SamplerSpec, resolve_spec, supported)

__all__ = ["BACKENDS", "DIFFUSIONS", "FRONTIERS", "Sampler", "SamplerSpec",
           "make_sampler", "resolve_spec", "supported"]
