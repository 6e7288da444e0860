"""Long-document QA traffic on a DeepSeek-V3-layout model (latent
attention, a held share of the routed experts): open-loop requests, each
one sequence, through `DeadlineScheduler.lm_prefill` / `lm_decode` on a
one-chip mesh, as `lm_chat` drives them.

The program's latent cache carries two counters of (token, expert) pairs
the held experts computed, one for the prefill and one summed over the
decode steps, per held expert.  They are copied to the host with the
first token (the prefill's) and with the last token (the decode steps'),
once each per request, and summed in `counters()` with the prefills'
lengths.  Nothing is copied per step beyond the token.

The check is chat's: the reference (`reference/mla_moe.py`) runs once over
each checked request's prompt and served tokens (padded to a multiple of
512 positions, so three shapes for the three prompt lengths; logits at the
served positions only), and the gaps by which each served token's logit
lies below the reference's best are read.  Two numbers are compared: the
widest gap (`limits.lm_doc.gap_logits`) and the mean gap over every
checked position (`gap_logits_mean`).  With random weights the routing of
some tokens sits at the top-6 boundary, and a rounding that swaps one of a
token's experts moves it by a whole expert's part however small the
rounding was; such swaps set the widest gap of a sound run now and then,
so the widest gap catches gross faults and the mean gap, which a few
swaps barely move, catches a program computing in a lower precision.
"""

from __future__ import annotations

import concurrent.futures as cf
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic
from bench.drivers import Comparison, lm_chat
from bench.reference import mla_moe as ref

PAD_TO = 512


def arch_config(cfg: Dict[str, Any]):
    """The program's `ArchConfig` for a DeepSeek-V3-layout configuration
    file: `n_routed_experts` experts held, at `deployment.held_offset` of
    `deployment.experts_published`."""
    from repro.models.config import ArchConfig, MLASpec, MoESpec

    if cfg.get("q_lora_rank") is not None:
        raise ValueError("queries through a low-rank projection are not implemented")
    if (cfg["n_group"], cfg["topk_group"], cfg["topk_method"],
            cfg["scoring_func"], cfg["norm_topk_prob"]) != (
                1, 1, "noaux_tc", "sigmoid", True):
        raise ValueError("only one-group sigmoid noaux_tc routing with "
                         "normalised top-k weights is implemented")
    run, dep = cfg["run"], cfg["deployment"]
    return ArchConfig(
        name=cfg.get("name", "lm"), family="transformer",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act=cfg["hidden_act"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        first_dense=cfg["first_k_dense_replace"],
        mla=MLASpec(kv_lora_rank=cfg["kv_lora_rank"],
                    qk_nope_head_dim=cfg["qk_nope_head_dim"],
                    qk_rope_head_dim=cfg["qk_rope_head_dim"],
                    v_head_dim=cfg["v_head_dim"]),
        moe=MoESpec(n_experts=dep["experts_published"],
                    top_k=cfg["num_experts_per_tok"],
                    d_ff_expert=cfg["moe_intermediate_size"],
                    scoring=cfg["scoring_func"],
                    routed_scale=float(cfg["routed_scaling_factor"]),
                    n_shared=cfg["n_shared_experts"],
                    n_held=cfg["n_routed_experts"],
                    held_offset=dep["held_offset"]),
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"])


class Driver(lm_chat.Driver):

    def setup(self) -> None:
        from repro.launch.mesh import make_mesh
        from repro.serve import DRService
        from repro.serve.scheduler import DeadlineScheduler

        self.arch = arch_config(self.cfg)
        self.params = ref.init_params(self.cfg, self.ctx.jax_key("weights"))
        self._moe = {"prefills": 0, "prefill_tokens": 0, "prefill_causal": 0,
                     "decode_steps": 0, "pairs_prefill": 0, "pairs_decode": 0,
                     "pairs_per_expert": np.zeros(self.arch.moe.e_held, np.int64)}
        m = self.cfg["run"]["mesh"]
        self.mesh = make_mesh(m["shape"], m["axes"],
                              devices=self.ctx.devices[:1])
        self.svc = DRService()
        self.sched = DeadlineScheduler(
            self.svc, default_max_delay_ms=self.spec["scheduler"]["max_delay_ms"])
        self.set_schedule(traffic.schedule(self.spec, self.ctx.seconds,
                                           self.size_keys))
        rng = self.ctx.rng("warm-up")
        self._prefill_warm_ms = {}
        for p in sorted(set(int(p) for p in
                            self.schedule.sizes["prompt_tokens"])):
            prompt = rng.integers(0, self.cfg["vocab_size"], size=p,
                                  dtype=np.int32)
            self._request(prompt, 2)
            t0 = time.perf_counter()
            self._request(prompt, 1)
            self._prefill_warm_ms[p] = (time.perf_counter() - t0) * 1e3
        self.pool = cf.ThreadPoolExecutor(int(self.spec["clients"]),
                                          thread_name_prefix="bench-client")

    def run(self, window):
        """Chat's window; the record also holds `prefill_warm_ms`, each
        prompt length's prefill timed alone in set-up, by which the
        readers tell the traced prefill programs apart."""
        out = super().run(window)
        self.record["prefill_warm_ms"] = dict(self._prefill_warm_ms)
        return out

    def counters(self) -> Dict[str, Any]:
        """Chat's counters, and the expert layer's: prefills, their tokens
        and causal (query, key) pairs, decode steps, and the (token,
        expert) pairs the held experts computed, in prefill, in decode,
        and per held expert (prefill and decode); all counted when a
        request's counter reaches the host."""
        out = super().counters()
        with self._lock:
            moe = dict(self._moe)
        moe["pairs_per_expert"] = [int(n) for n in moe["pairs_per_expert"]]
        out.update(moe)
        return out

    def _count(self, **kw) -> None:
        with self._lock:
            for k, v in kw.items():
                self._moe[k] = self._moe[k] + v

    def _request(self, prompt: np.ndarray, n_tokens: int,
                 contexts: Optional[List[int]] = None):
        """Chat's request, reading the expert counters with the first and
        the last token."""
        cache_size = int(self.spec["cache_size"])
        s = len(prompt)
        with jax.profiler.TraceAnnotation("bench.lm_prefill_submit"):
            t = self.sched.lm_prefill(self.arch, self.mesh, self.params,
                                      {"tokens": jnp.asarray(prompt[None])},
                                      cache_size)
        tokens, times = [], []
        for k in range(n_tokens):
            with jax.profiler.TraceAnnotation("bench.wait"):
                if not t.wait(lm_chat.WAIT_S):
                    raise TimeoutError("an LM step was not served")
            logits, cache = t.result()
            last = k + 1 == n_tokens
            with jax.profiler.TraceAnnotation("bench.argmax_to_host"):
                tok = lm_chat._argmax(logits)
                want = [tok]
                if k == 0:
                    want.append(cache["pairs_prefill"])
                if last:
                    want.append(cache["pairs_decode"])
                got = jax.device_get(want)
                tokens.append(int(got[0][0]))
            times.append(time.perf_counter())
            with self._lock:
                self._steps += 1
            if k == 0:
                pp = np.asarray(got[1], np.int64)
                self._count(prefills=1, prefill_tokens=s,
                            prefill_causal=s * (s + 1) // 2,
                            pairs_prefill=int(pp.sum()), pairs_per_expert=pp)
            if last:
                pd = np.asarray(got[-1], np.int64)
                self._count(decode_steps=n_tokens - 1,
                            pairs_decode=int(pd.sum()), pairs_per_expert=pd)
                break
            if contexts is not None:
                contexts.append(s + k + 1)
            with jax.profiler.TraceAnnotation("bench.lm_decode_submit"):
                t = self.sched.lm_decode(self.arch, self.mesh, self.params,
                                         tok, cache)
        return tokens, times

    # ---- the check ---------------------------------------------------------
    def gaps(self, i: int, control: bool) -> np.ndarray:
        """Chat's gaps at each served position of request i, against the
        latent-attention reference."""
        prompt = self.prompts[i]
        toks = self.record["tokens"][i]
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        n = len(seq)
        seq = jnp.asarray(np.pad(seq, (0, -(-n // PAD_TO) * PAD_TO - n)))
        rows = dict(first=len(prompt) - 1, count=len(toks))
        want = np.asarray(ref.forward(self.params, seq, self.cfg, **rows),
                          np.float64)
        if control:
            od = lm_chat.CONTROL_DTYPE[self.cfg["precision"]["mxu_operands"]]
            low = np.asarray(ref.forward(self.params, seq, self.cfg, od,
                                         **rows))
            served = np.argmax(low, axis=-1)
        else:
            served = np.asarray(toks)
        if not np.all(np.isfinite(want)):
            return np.full(len(served), np.inf)
        best = want.max(axis=-1)
        return best - want[np.arange(len(served)), served]

    def check(self, mode: str = "program") -> List[Comparison]:
        ids = self.sample()
        gaps = [self.gaps(i, mode == "control") for i in ids]
        every = np.concatenate(gaps) if gaps else np.array([np.inf])
        lim = self.cfg["limits"]["lm_doc"]
        return [Comparison("gap_logits", float(every.max()),
                           float(lim["gap_logits"])),
                Comparison("gap_logits_mean", float(every.mean()),
                           float(lim["gap_logits_mean"]))]
