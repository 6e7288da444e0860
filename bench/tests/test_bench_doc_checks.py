"""The long-document QA cell's check: a sound run passes and reports its
end-to-end metrics, the control in lower precision reads wider gaps than
the program (widest and mean), and a token altered where it is produced
fails the check.

Each drives a whole run past the harness's look for a chip, on the CPU,
with the configuration's layer pattern (one dense layer, then MoE layers
with a held share of the experts) at a small width, weights in bfloat16
as the configuration stores them; the grouped matmul runs in interpret
mode.
"""

from __future__ import annotations

SMALL_DOC = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 4, "kv_lora_rank": 32,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "intermediate_size": 96,
             "moe_intermediate_size": 32, "num_experts_per_tok": 3,
             "n_routed_experts": 2, "num_hidden_layers": 3,
             "vocab_size": 512,
             "deployment": {"experts_published": 8, "held_offset": 2}}
SPEC = {"rate_per_s": 6.0, "cache_size": 64, "check_requests": 3,
        "prompt_tokens": {"dist": "choice", "values": [16, 40]},
        "output_tokens": {"dist": "fixed", "value": 8}}


def _run(cell_run, seconds=0.7):
    return cell_run("moonlight_16b_a3b.doc_qa", seconds=seconds, spec=SPEC,
                    config=SMALL_DOC)


def test_sound_run_is_correct_and_reports_its_metrics(cell_run):
    out = _run(cell_run)
    assert out["correct"], out["checked"]
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_ms", "itl_p99_ms", "setup_s"}


def test_control_in_lower_precision_reads_wider_gaps(cell_run, monkeypatch):
    from bench.drivers import lm_doc

    seen = {}
    real = lm_doc.Driver.check

    def both(self, mode="program"):
        seen["program"] = [c.value for c in real(self, "program")]
        seen["control"] = [c.value for c in real(self, "control")]
        return real(self, "program")

    monkeypatch.setattr(lm_doc.Driver, "check", both)
    _run(cell_run)
    (widest, mean), (c_widest, c_mean) = seen["program"], seen["control"]
    assert c_widest > widest and c_mean > mean
    assert c_mean > 0


def test_a_token_altered_where_it_is_produced_fails(cell_run, monkeypatch):
    from repro.models import transformer

    real = transformer.decode_step

    def broken(params, token, cache, cfg):
        logits, cache = real(params, token, cache, cfg)
        return logits.at[:, 7].add(1e4), cache

    monkeypatch.setattr(transformer, "decode_step", broken)
    out = _run(cell_run)
    assert not out["correct"]
    assert out["checked"]["gap_logits"]["value"] > 1.0


def test_the_expert_counters_reach_the_host(cell_run, monkeypatch):
    """Every request's prefill and decode pairs are counted once: a
    prefill of s tokens routes s * k pairs, and the held share's part of
    them lies between none and all."""
    from bench.drivers import lm_doc

    got = {}
    real = lm_doc.Driver.close

    def keep(self):
        got.update(self.counters())
        return real(self)

    monkeypatch.setattr(lm_doc.Driver, "close", keep)
    _run(cell_run)
    k = SMALL_DOC["num_experts_per_tok"]
    assert got["prefills"] >= 2 and got["decode_steps"] > 0
    assert 0 < got["pairs_prefill"] < got["prefill_tokens"] * k
    assert sum(got["pairs_per_expert"]) == got["pairs_prefill"] + got["pairs_decode"]
    assert len(got["pairs_per_expert"]) == SMALL_DOC["n_routed_experts"]


def test_traced_prefill_runs_take_their_prompt_lengths():
    """Each traced prefill program takes the prompt length whose set-up
    time lies nearest its runs' mean, and the prefill and kernel shares
    are read from the traced runs alone, not from counters taken at the
    trace's edges."""
    from bench import run as bench_run
    from bench import trace
    from bench.metrics import doc_runs

    ms = {"jit_fn(7)": (5.0, 40), "jit_fn(3)": (78.0, 2), "jit_fn(9)": (340.0, 1)}
    modules, t = [], 0.0
    for name, (dur, count) in ms.items():
        for _ in range(count):
            modules.append(trace.Event(name, t, dur * 1e6))
            t += dur * 1e6 + 1e5
    ops = [trace.Event('%gmm.1 = bf16[6,1408] custom-call(), '
                       'custom_call_target="tpu_custom_call"', 0.0, 1e6)]
    tr = trace.Summary(window_s=2.0, busy_s=t * 1e-9, modules=modules,
                       ops=ops, idle_gaps=[])
    cfg = bench_run.resolve(bench_run.load_benchmark(),
                            "moonlight_16b_a3b.doc_qa")["config"]
    counters = ({"prefill_tokens": 0, "pairs_prefill": 0, "decode_steps": 0,
                 "pairs_decode": 0},
                {"prefill_tokens": 10000, "pairs_prefill": 7500,
                 "decode_steps": 1000, "pairs_decode": 750})
    ctx = {"trace": tr, "config": cfg, "counters": counters,
           "trace_counters": None, "device_kind": "TPU v5 lite",
           "record": {"prefill_warm_ms": {"2048": 82.0, "4096": 165.0,
                                          "8128": 362.0}}}
    assert sorted(doc_runs.traced_prefills(ctx)) == [
        (2048, 0.078), (2048, 0.078), (8128, 0.34)]
    assert doc_runs.pairs_per_token(ctx) == 0.75
    for name in ("prefill_mfu.doc", "moe_gmm_roofline.doc"):
        value = bench_run.load_reader(name)(ctx)
        assert value is not None and 0.0 < value <= 100.0, (name, value)
    assert doc_runs.traced_prefills(dict(ctx, record={})) is None
