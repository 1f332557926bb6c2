"""A whole run at a tiny size on the CPU, past the harness's look for a
chip, with the served path broken underneath: ``correct`` has to come out
false for each fault a serving cell can have. (Replicas exchange nothing
between chips, so a dropped exchange is not among them.)"""
import jax
import jax.numpy as jnp
import pytest

from bench.run import run_cell
from repro.core import engine as engine_mod
from repro.models.transformer import LM


def _run(root, bench, cell, seed=2**31 + 101):
    return run_cell(root, bench, cell, seed=seed, seconds=2.0, trace=False,
                    devices=jax.devices())


def test_sound_run_is_correct(tiny_root):
    res = _run(*tiny_root)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check" and res["check"]["checked_tokens"]["value"] > 0
    assert set(res["metrics"]) == {"output_tok_s", "itl_p99_ms", "setup_s"}
    assert res["device"]["platform"] == "cpu" and res["attempted"] > 0


def _state_unchanged(monkeypatch):
    """A step that returns its KV state unchanged: nothing is written."""
    real = LM.decode_chunk

    def broken(self, params, tokens, cache, *a, **k):
        logits, _ = real(self, params, tokens, cache, *a, **k)
        return logits, cache
    monkeypatch.setattr(LM, "decode_chunk", broken)


def _half_batch(monkeypatch):
    """Half of the batch left out: the second half of a call's rows take the
    first half's results."""
    real = LM.decode_chunk

    def broken(self, params, tokens, cache, *a, **k):
        logits, cache = real(self, params, tokens, cache, *a, **k)
        h = logits.shape[0] // 2
        return jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]), cache
    monkeypatch.setattr(LM, "decode_chunk", broken)


def _token_altered(monkeypatch):
    """A token altered where it is produced."""
    real = engine_mod.sample_tokens

    def broken(logits, *a, **k):
        return (real(logits, *a, **k) + 1) % logits.shape[-1]
    monkeypatch.setattr(engine_mod, "sample_tokens", broken)


def _one_slot(monkeypatch):
    """A token altered where it is produced, in one slot of the engine's four
    alone: that slot's rows put their second-best token first."""
    real = LM.decode_chunk

    def broken(self, params, tokens, cache, starts, nvalid, slots, *a, **k):
        logits, cache = real(self, params, tokens, cache, starts, nvalid, slots, *a, **k)
        return jnp.where((slots == 0)[:, None], jnp.roll(logits, 1, axis=-1), logits), cache
    monkeypatch.setattr(LM, "decode_chunk", broken)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _token_altered, _one_slot],
                         ids=["state_unchanged", "half_batch", "token_altered", "one_slot"])
def test_fault_makes_the_run_incorrect(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(*tiny_root)
    assert not res["correct"]
    gap = res["check"]["gap_p90"]          # the committed Mixtral cells' number
    assert gap["value"] is None or gap["value"] > gap["limit"]
