"""CPU rehearsal of ``rmat-lt-wc.build`` at a small scale through
``bench/run.py``'s ``execute``: correct with the program as it is, and not
correct under each fault the cell has to catch — the destination-keyed
pick (the reversed edge's destination choosing among its in-edges), the
bfloat16 control, and one flipped bit in one set.  The harness's look for
a chip is skipped."""
from __future__ import annotations

import dataclasses
from functools import partial
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests.rehearsal import rehearse

CELL = "rmat-lt-wc.build"


def test_lt_cell_rehearses_correct():
    line, run = rehearse(CELL)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] >= 2 * 64
    assert list(line)[-1] == "checks"
    assert {"rrr_sets_per_s", "setup_s"} <= set(line["metrics"])
    c = run.counters
    assert c["levels"] >= c["batches"] > 0
    assert c["truncated_colors"] == 0
    assert 0 < c["fused_edge_visits"] <= c["levels"] * c["edges_per_level"]


def _read(name, run):
    return harness.load_module(
        harness.BENCH / "metrics" / f"{name}.py").read(run)


def test_lt_readers_read_the_window_and_nothing_without_it():
    """The three ``lt_*`` readers from a window's counters and its trace's
    ``run_fused_lt_block`` time; nothing without the program's runs, nor
    from a program whose LT block counts no edge visits."""
    trace = SimpleNamespace(program_seconds=lambda pattern: (
        (6.0, 3) if "run_fused_lt_block" in pattern else (0.0, 0)))
    counters = dict(batches=3, levels=300, fused_edge_visits=1500,
                    edges_per_level=100)
    run = SimpleNamespace(trace_summary=trace, counters=counters)
    assert _read("lt_traversal_ms_per_batch", run) == pytest.approx(2000.0)
    assert _read("lt_ms_per_level", run) == pytest.approx(20.0)
    assert _read("lt_sweep_useful_share", run) == pytest.approx(5.0)
    silent = SimpleNamespace(
        trace_summary=SimpleNamespace(program_seconds=lambda p: (0.0, 0)),
        counters=dict(counters, fused_edge_visits=None))
    for name in ("lt_traversal_ms_per_batch", "lt_ms_per_level",
                 "lt_sweep_useful_share"):
        assert _read(name, silent) is None


@partial(jax.jit, static_argnames=("num_colors", "max_levels"))
def _dst_keyed(g, picker, cb, starts, seeds, num_colors, max_levels):
    from repro.core import lt

    def one(args):
        st, sd = args
        sel = lt.selection_mask_from_cb(picker, cb, num_colors, sd)
        vis, levels, truncated, visits = lt.lt_traversal_program(
            g, sel, st, num_colors, max_levels)
        return vis, visits, jnp.stack([levels, truncated])

    return jax.lax.map(one, (starts, seeds))


def _dst_keyed_pick(mp):
    """The block program with each reversed edge's DESTINATION picking
    among its in-edges of the reversed graph (forward out-edges)."""
    from repro.core import lt

    def block(g, cb, starts, seeds, num_colors, max_levels=64,
              segments=None):
        picker = lt.normalize_lt_weights(
            dataclasses.replace(g, src=g.dst, dst=g.src))
        cb_dst = jnp.asarray(lt.selection_cum_before(picker))
        return _dst_keyed(g, picker, cb_dst, starts, seeds, num_colors,
                          max_levels)

    mp.setattr(lt, "run_fused_lt_block", block)


def _flipped_bit(mp):
    """One bit of one RRR set flipped where the block is produced."""
    from repro.core import lt
    block = lt.run_fused_lt_block

    def flipped(*a, **k):
        vis, visits, counts = block(*a, **k)
        return vis.at[0, 5, 0].set(vis[0, 5, 0] ^ jnp.uint32(1)), visits, \
            counts

    mp.setattr(lt, "run_fused_lt_block", flipped)


@pytest.mark.parametrize("fault,control", [
    (_dst_keyed_pick, None),
    (None, "bf16"),
    (_flipped_bit, None),
], ids=["dst_keyed_pick", "bf16_control", "flipped_bit"])
def test_lt_fault_fails_correct(fault, control, monkeypatch):
    """The comparison with the reference catches each fault."""
    if fault is not None:
        fault(monkeypatch)
    line, _ = rehearse(CELL, control=control)
    assert not line["correct"], line["checks"]
    assert line["checks"]["sets_differing"]["value"] > 0
