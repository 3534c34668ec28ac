"""The check that decides ``correct`` fails a broken timed path.

The harness runs as the benchmark does, past its look for a chip, with
the program broken underneath: every served token altered where the
engine produces it. The only fault of the kinds the contract lists that a
one-chip serving cell can have (there is no training step, no batch mean
and no exchange between chips)."""
import pytest

import bench_smoke


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bench_smoke.make_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", bench_smoke.cells())
def test_altered_tokens_come_out_not_correct(root, cell, monkeypatch):
    import repro.serve.engine as engine
    sample = engine._sample_tokens

    def altered(key, logits, temps, slot_ids):
        key, toks = sample(key, logits, temps, slot_ids)
        return key, (toks + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "_sample_tokens", altered)
    result = bench_smoke.run(root, cell)
    gap = result["checks"]["mean_logit_gap"]
    assert result["correct"] is False
    assert gap["value"] > gap["limit"]
