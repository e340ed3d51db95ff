"""A whole run of a cell with its timed path broken underneath must come
out not correct: once for each fault the cell can have.  The harness's
look for a chip is skipped (CPU rehearsal at a tiny size)."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench.tests.rehearsal import rehearse

BUILD = {"pool_batches": 2}


def _unchanged_state(mp):
    """A refresh that returns its slots but leaves the pool as it was."""
    from repro.serve.influence import SketchStore
    mp.setattr(SketchStore, "refresh",
               lambda self, fraction=0.25: list(range(len(self.batches))))


def _wrap_block(mp, edit):
    """Edit the visited masks where a block of batches is produced."""
    from repro.core import traversal
    dense = traversal.run_fused_block

    def dense_block(*a, **k):
        vis, fused, unfused = dense(*a, **k)
        return edit(vis), fused, unfused

    mp.setattr(traversal, "run_fused_block", dense_block)


def _half_left_out(mp):
    """Colors 32–63 of every batch left out."""
    _wrap_block(mp, lambda vis: vis.at[:, :, 1].set(0))


def _answer_altered(mp):
    """One bit of one RRR set flipped where the block is produced."""
    _wrap_block(mp, lambda vis: vis.at[0, 5, 0].set(
        vis[0, 5, 0] ^ jnp.uint32(1)))


@pytest.mark.parametrize("cell", ["rmat-ic-wc.build", "rmat-ic-uni.build"])
@pytest.mark.parametrize("fault", [_unchanged_state, _half_left_out,
                                   _answer_altered])
def test_build_fault_is_caught(cell, fault, monkeypatch):
    fault(monkeypatch)
    line, _ = rehearse(cell, traffic=dict(BUILD, check_edge_budget=10**9))
    assert not line["correct"], line["checks"]
