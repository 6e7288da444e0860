"""The chat cell's check: a sound run passes, the control in lower
precision reads wider gaps than the program, and a token altered where it
is produced fails the check.

Each drives a whole run past the harness's look for a chip, on the CPU,
with the layer pattern of the configuration at a small width.
"""

from __future__ import annotations

from bench.tests.conftest import SMALL_LM

SPEC = {"rate_per_s": 6.0, "cache_size": 64, "check_requests": 3,
        "prompt_tokens": {"dist": "choice", "values": [16, 32]},
        "output_tokens": {"dist": "fixed", "value": 12}}


def _run(cell_run, seconds=0.7):
    return cell_run("smollm_135m.chat", seconds=seconds, spec=SPEC,
                    config=SMALL_LM)


def test_sound_run_is_correct_and_reports_its_metrics(cell_run):
    out = _run(cell_run)
    assert out["correct"], out["checked"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p99_ms", "setup_s"}


def test_control_in_lower_precision_reads_wider_gaps(cell_run, monkeypatch):
    from bench.drivers import lm_chat

    seen = {}
    real = lm_chat.Driver.check

    def both(self, mode="program"):
        seen["program"] = real(self, "program")[0].value
        seen["control"] = real(self, "control")[0].value
        return real(self, "program")

    monkeypatch.setattr(lm_chat.Driver, "check", both)
    _run(cell_run)
    assert seen["control"] > seen["program"]
    assert seen["control"] > 0


def test_a_token_altered_where_it_is_produced_fails(cell_run, monkeypatch):
    from repro.models import transformer

    real = transformer.decode_step

    def broken(params, token, cache, cfg):
        logits, cache = real(params, token, cache, cfg)
        return logits.at[:, 7].add(1e4), cache

    monkeypatch.setattr(transformer, "decode_step", broken)
    out = _run(cell_run)
    assert not out["correct"]
    assert out["checked"]["gap_logits"]["value"] > 4.0
