"""Optimizer, compression, data pipeline, checkpoint, serving engine."""
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.checkpoint import manager as ckpt
from repro.configs import registry
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.models import model
from repro.optim import adamw, compress
from repro.serve import engine
from repro.train.step import make_train_step


# ------------------------------------------------------------------- AdamW
def test_adamw_descends_quadratic():
    params = {"w": jnp.asarray([5.0, -3.0, 2.0])}
    opt = adamw.init(params)
    lr_fn = lambda s: 0.1
    for _ in range(200):
        grads = jax.grad(lambda p: jnp.sum(jnp.square(p["w"])))(params)
        params, opt, _ = adamw.update(params, grads, opt, lr=0.1,
                                      weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 1e-2


def test_grad_clip():
    grads = {"a": jnp.full((4,), 100.0)}
    clipped, norm = adamw.clip_by_global_norm(grads, 1.0)
    assert abs(float(adamw.global_norm(clipped)) - 1.0) < 1e-5
    assert float(norm) == pytest.approx(200.0)


def test_cosine_schedule_shape():
    lr = adamw.cosine_schedule(1e-3, warmup=10, total=100)
    assert float(lr(0)) == 0.0
    assert float(lr(10)) == pytest.approx(1e-3)
    assert float(lr(100)) == pytest.approx(1e-4, rel=0.01)
    assert float(lr(55)) < float(lr(20))


# ------------------------------------------------------------- compression
def test_quantize_roundtrip_error_bounded():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 3
    q, scale = compress.quantize(x)
    err = np.abs(np.asarray(compress.dequantize(q, scale) - x))
    assert err.max() <= float(scale) * 0.5 + 1e-6


def test_compressed_psum_matches_exact_within_quantization():
    """Run under shard_map on a 1-device mesh (semantics identical)."""
    mesh = make_mesh((1,), ("data",))
    g = {"w": jax.random.normal(jax.random.key(1), (256,))}

    def body(gr):
        mean, res = compress.compressed_psum(gr, "data")
        return mean, res

    from jax.sharding import PartitionSpec as P
    mean, res = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()),
        check_vma=False))(g)
    np.testing.assert_allclose(np.asarray(mean["w"] + res["w"]),
                               np.asarray(g["w"]), atol=1e-6)
    # error feedback residual is bounded by half a quantization level
    _, scale = compress.quantize(g["w"])
    assert float(jnp.abs(res["w"]).max()) <= float(scale) * 0.5 + 1e-6


def test_error_feedback_converges():
    """SGD + int8 compression + error feedback still drives a quadratic to
    zero (compression alone would stall at the quantization floor)."""
    w = jnp.asarray([2.0, -1.5])
    err = jnp.zeros_like(w)
    for _ in range(300):
        g = 2 * w
        q, scale = compress.quantize(g + err)
        g_hat = compress.dequantize(q, scale)
        err = (g + err) - g_hat
        w = w - 0.05 * g_hat
    assert float(jnp.abs(w).max()) < 1e-2


# ------------------------------------------------------------------- data
def test_pipeline_deterministic_by_step():
    cfg = registry.smoke("llama3.2-3b")
    d1 = SyntheticLM(cfg, 4, 32, seed=7)
    d2 = SyntheticLM(cfg, 4, 32, seed=7)
    b1, b2 = d1.batch_at(13), d2.batch_at(13)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert not np.array_equal(b1["tokens"], d1.batch_at(14)["tokens"])


def test_pipeline_labels_shifted():
    cfg = registry.smoke("llama3.2-3b")
    b = SyntheticLM(cfg, 2, 16, seed=0).batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_prefetcher_order_and_resume():
    cfg = registry.smoke("llama3.2-3b")
    src = SyntheticLM(cfg, 2, 16, seed=3)
    pf = Prefetcher(src, start_step=5)
    try:
        for expect in (5, 6, 7):
            step, batch = pf.get()
            assert step == expect
            np.testing.assert_array_equal(batch["tokens"],
                                          src.batch_at(expect)["tokens"])
    finally:
        pf.close()


# -------------------------------------------------------------- checkpoint
def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)},
            "list": [jnp.zeros((2,)), jnp.full((3,), 7)]}
    ckpt.save(str(tmp_path), 3, tree)
    restored, step = ckpt.restore(str(tmp_path), tree)
    assert step == 3
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.dtype == b.dtype


def test_checkpoint_keeps_last_k(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    for s in range(6):
        ckpt.save(str(tmp_path), s, tree, keep=2)
    assert ckpt.latest_step(str(tmp_path)) == 5
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(dirs) == 2


def test_checkpoint_async(tmp_path):
    tree = {"x": jnp.arange(10)}
    t = ckpt.save(str(tmp_path), 1, tree, blocking=False)
    t.join()
    restored, _ = ckpt.restore(str(tmp_path), tree)
    np.testing.assert_array_equal(np.asarray(restored["x"]),
                                  np.asarray(tree["x"]))


def test_checkpoint_ignores_partial_tmp(tmp_path):
    tree = {"x": jnp.zeros((2,))}
    ckpt.save(str(tmp_path), 1, tree)
    os.makedirs(tmp_path / "step_00000002.tmp")      # simulated dead writer
    assert ckpt.latest_step(str(tmp_path)) == 1


# ------------------------------------------------------------- train step
def test_train_step_reduces_loss():
    cfg = registry.smoke("llama3.2-3b")
    params = model.init_params(jax.random.key(0), cfg)
    opt = adamw.init(params)
    data = SyntheticLM(cfg, 8, 32, seed=1)
    step_fn = jax.jit(make_train_step(cfg, lambda s: 1e-3))
    first = last = None
    for step in range(30):
        b = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
        params, opt, m = step_fn(params, opt, b)
        if step == 0:
            first = float(m["loss"])
        last = float(m["loss"])
    assert last < first - 0.5, (first, last)


def test_microbatched_grads_match_full_batch():
    cfg = registry.smoke("llama3.2-3b")
    params = model.init_params(jax.random.key(0), cfg)
    data = SyntheticLM(cfg, 8, 32, seed=2)
    b = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    opt = adamw.init(params)
    p1, _, m1 = jax.jit(make_train_step(cfg, lambda s: 1e-3, 1))(params, opt, b)
    p2, _, m2 = jax.jit(make_train_step(cfg, lambda s: 1e-3, 4))(params, opt, b)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    for a, c in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(c, np.float32), atol=2e-5)


# ---------------------------------------------------------------- serving
@pytest.mark.parametrize("arch", ["llama3.2-3b", "mamba2-1.3b",
                                  "zamba2-2.7b", "musicgen-medium"])
def test_generate_greedy_matches_teacher_forced(arch):
    """prefill+decode generation equals argmax over the forward logits when
    re-scoring the generated sequence (cache correctness end-to-end)."""
    import dataclasses
    cfg = dataclasses.replace(registry.smoke(arch), capacity_factor=8.0)
    params = model.init_params(jax.random.key(0), cfg)
    B, Lp, n_new = 2, 8, 4
    rng = np.random.default_rng(0)
    shape = ((B, cfg.num_codebooks, Lp) if cfg.num_codebooks else (B, Lp))
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, shape))
    out = engine.generate(params, cfg, prompt, n_new, temperature=0.0)
    full = jnp.concatenate([prompt, out], -1 if cfg.num_codebooks else 1)
    logits, _, _ = model.forward(params, cfg,
                                 {"tokens": full, "labels": full})
    # position Lp-1+i predicts generated token i
    for i in range(n_new):
        pred = jnp.argmax(logits[:, Lp - 1 + i], -1)
        got = out[..., i] if cfg.num_codebooks else out[:, i]
        if cfg.num_codebooks:
            np.testing.assert_array_equal(np.asarray(pred),
                                          np.asarray(got))
        else:
            np.testing.assert_array_equal(np.asarray(pred), np.asarray(got))
