"""The serving cell's check, driven end to end on the CPU at a small size
with the look for a chip skipped: a sound run is correct, and a token
altered where it is produced makes it come out false."""

import chipbench_tiny as tiny

CELL = "qwen2_serve_decode"


def test_sound_run_is_correct(tmp_path):
    r = tiny.run(str(tmp_path), CELL, seconds=1.0)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    m = r["metrics"]
    assert m["itl_p95_ms"]["value"] > 0 and "ttft_p90_ms" not in m


def test_altered_token_fails(tmp_path, monkeypatch):
    import jax.numpy as jnp
    from repro.serve import engine
    pick = engine._pick_tokens

    def altered(logits, cfg, rids, pos):
        tok = pick(logits, cfg, rids, pos)
        return jnp.where(pos == 1, (tok + 1) % logits.shape[-1], tok)
    monkeypatch.setattr(engine, "_pick_tokens", altered)
    r = tiny.run(str(tmp_path), CELL, seconds=1.0)
    assert not r["correct"], r["compared"]
