"""Data-parallel training step with int8-compressed gradient all-reduce.

The cross-pod DP all-reduce is the dominant collective at 1000+-node scale;
this step runs the whole update under shard_map so the reduction is
explicit and swappable:

    exact      — pmean(grads)                        (fp32 wire bytes)
    compressed — int8 quantize + psum + error feedback (≈¼ wire bytes)

Params/optimizer state are replicated across the DP axis (this step is the
*pure-DP* regime — small/medium models or the pod axis of a larger mesh);
the per-device quantization residual rides in the optimizer extras with a
leading device axis, sharded on the DP axis, so it stays device-local.

Convergence with compression is protected by error feedback — validated in
tests/test_dp_compression.py (loss curve within noise of the exact step).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import model
from repro.models.config import ModelConfig
from repro.optim import adamw, compress


def make_dp_train_step(cfg: ModelConfig, lr_fn, mesh, axis: str = "data",
                       compressed: bool = True, weight_decay: float = 0.1):
    """Returns (step_fn, init_residual).  step_fn(params, opt, err, batch)
    → (params, opt, err, metrics); batch's leading dim is sharded on
    ``axis``; err leaves have leading dim = axis size (device-local)."""
    n_dev = mesh.shape[axis]

    def loss_of(p, mb):
        return model.loss_fn(p, cfg, mb)[0]

    def body(params, opt_state, err, batch):
        loss, g = jax.value_and_grad(loss_of)(params, batch)
        loss = jax.lax.pmean(loss, axis)
        if compressed:
            err0 = jax.tree.map(lambda e: e[0], err)
            g_in = jax.tree.map(lambda a, b: a.astype(jnp.float32) + b,
                                g, err0)
            g_hat, res = compress.compressed_psum(g_in, axis)
            err = jax.tree.map(lambda r: r[None], res)
        else:
            g_hat = jax.tree.map(lambda a: jax.lax.pmean(
                a.astype(jnp.float32), axis), g)
        lr = lr_fn(opt_state.step)
        params, opt_state, gnorm = adamw.update(
            params, g_hat, opt_state, lr=lr, weight_decay=weight_decay)
        return params, opt_state, err, {"loss": loss, "grad_norm": gnorm}

    rep = P()
    err_spec = jax.tree.map(lambda _: P(axis), _err_structure(cfg))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(rep, rep, err_spec, P(axis)),
        out_specs=(rep, rep, err_spec, rep),
        check_vma=False)

    def init_residual(params):
        return jax.tree.map(
            lambda p: jax.device_put(
                jnp.zeros((n_dev, *p.shape), jnp.float32),
                NamedSharding(mesh, P(axis))), params)

    return jax.jit(fn), init_residual


def _err_structure(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: model.init_params(jax.random.key(0), cfg))
