"""A whole run of a (tiny) cell, past the harness's look for a chip, with
the timed path broken underneath: `correct` must come out false for each
fault a serving cell can have, and true with none."""
import jax
import jax.numpy as jnp
import pytest

from bench import run as R
from bench.tests import tiny
from repro.launch.engine import ServingEngine

_step_fn = ServingEngine._step_fn


def _planted(fault):
    """ServingEngine._step_fn with `fault` planted in the step it returns."""
    def step_fn(self, t):
        inner = _step_fn(self, t)

        def step(params, caches, tokens, lengths, n_new, block_tables):
            s = tokens.shape[0]
            if fault == "half_batch":
                # half of the slots is left out of the step
                n_new = jnp.where(jnp.arange(s) < s // 2, 0, n_new)
            keep = jax.tree_util.tree_map(jnp.copy, caches)
            nxt, caches = inner(params, caches, tokens, lengths, n_new,
                                block_tables)
            if fault == "state_unchanged":
                caches = keep
            if fault == "token_altered":
                nxt = nxt.at[0].set((nxt[0] + 1) % self.model.cfg.vocab)
            return nxt, caches
        return step
    return step_fn


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch",
                                   "token_altered"])
def test_fault_turns_correct_false(fault, monkeypatch, tmp_path):
    if fault is not None:
        monkeypatch.setattr(ServingEngine, "_step_fn", _planted(fault))
    res = R.run_cell(tiny.cell(), 2**31 + 9, 1.0, False,
                     require_chip=False, out_dir=tmp_path)
    assert res["correct"] is (fault is None), res["check"]
    assert res["check"]["tokens_compared"]["value"] > 0
