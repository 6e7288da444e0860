"""Chat traffic: open-loop requests, each one sequence, through
`DeadlineScheduler.lm_prefill` / `lm_decode` on a one-chip mesh.

A request's prompt is its `prompt_tokens` ids from the seed.  Its first
token is the greedy argmax of the prefill's logits, and each later one the
argmax of a decode step fed the token before; every token is copied to
the host as it is produced.  One generator thread hands each request at
its due time to a pool of `clients` threads (a request waits in the pool's
queue when all are busy).  Time to first token runs from the due time to
the first token on the host; the gap between tokens is taken between
consecutive tokens of one request on the host.

The check draws from the seed `check_requests` finished requests, the
longest prompt among them, runs the reference once over each prompt with
its served tokens, and reads the widest gap by which a served token's
logit lies below the reference's best at that position.
"""

from __future__ import annotations

import concurrent.futures as cf
import threading
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import stats, traffic
from bench.drivers import Comparison, Context, Window, sleep_until
from bench.reference import lm as ref

WAIT_S = 60.0
CONTROL_DTYPE = {"bfloat16": jnp.float8_e4m3fn}


@jax.jit
def _argmax(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def arch_config(cfg: Dict[str, Any]):
    """The program's `ArchConfig` for a Llama-layout configuration file."""
    from repro.models.config import ArchConfig

    run = cfg["run"]
    return ArchConfig(
        name=cfg.get("name", "lm"), family="transformer",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), act=cfg["hidden_act"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=run["param_dtype"], compute_dtype=run["compute_dtype"])


class Driver:
    size_keys = ("prompt_tokens", "output_tokens")

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.spec = ctx.spec
        self.cfg = ctx.config
        self.record: Dict[str, Any] = {}
        self.sched = None
        self.pool: Optional[cf.ThreadPoolExecutor] = None
        self._lock = threading.Lock()
        self._steps = 0

    def setup(self) -> None:
        from repro.launch.mesh import make_mesh
        from repro.serve import DRService
        from repro.serve.scheduler import DeadlineScheduler

        self.arch = arch_config(self.cfg)
        self.params = ref.init_params(self.cfg, self.ctx.jax_key("weights"))
        m = self.cfg["run"]["mesh"]
        self.mesh = make_mesh(m["shape"], m["axes"],
                              devices=self.ctx.devices[:1])
        self.svc = DRService()
        self.sched = DeadlineScheduler(
            self.svc, default_max_delay_ms=self.spec["scheduler"]["max_delay_ms"])
        self.set_schedule(traffic.schedule(self.spec, self.ctx.seconds,
                                           self.size_keys))
        # every prompt length the traffic sends, and the decode step
        rng = self.ctx.rng("warm-up")
        for p in sorted(set(int(p) for p in
                            self.schedule.sizes["prompt_tokens"])):
            self._request(rng.integers(0, self.cfg["vocab_size"], size=p,
                                       dtype=np.int32), 2)
        self.pool = cf.ThreadPoolExecutor(int(self.spec["clients"]),
                                          thread_name_prefix="bench-client")

    def set_schedule(self, schedule: traffic.Schedule) -> None:
        """The requests the window sends, with their prompts' ids."""
        self.schedule = schedule
        rng = self.ctx.rng("prompts")
        v = self.cfg["vocab_size"]
        self.prompts = [rng.integers(0, v, size=int(p), dtype=np.int32)
                        for p in schedule.sizes["prompt_tokens"]]

    def counters(self) -> Dict[str, Any]:
        """Steps served, and the service's stages' and SLO cells'
        histograms (`stages`, `slo`)."""
        with self._lock:
            steps = self._steps
        return {"steps": steps, "stages": self.svc.slo.stages(),
                "slo": self.svc.slo.snapshot()}

    def _request(self, prompt: np.ndarray, n_tokens: int,
                 contexts: Optional[List[int]] = None):
        """Prefill, then greedy decode to `n_tokens` tokens; returns
        (tokens, host arrival time of each)."""
        cache_size = int(self.spec["cache_size"])
        with jax.profiler.TraceAnnotation("bench.lm_prefill_submit"):
            t = self.sched.lm_prefill(self.arch, self.mesh, self.params,
                                      {"tokens": jnp.asarray(prompt[None])},
                                      cache_size)
        tokens, times = [], []
        for k in range(n_tokens):
            with jax.profiler.TraceAnnotation("bench.wait"):
                if not t.wait(WAIT_S):
                    raise TimeoutError("an LM step was not served")
            logits, cache = t.result()
            with jax.profiler.TraceAnnotation("bench.argmax_to_host"):
                tok = _argmax(logits)
                tokens.append(int(np.asarray(tok)[0]))
            times.append(time.perf_counter())
            with self._lock:
                self._steps += 1
            if k + 1 == n_tokens:
                break
            if contexts is not None:
                contexts.append(len(prompt) + k + 1)
            with jax.profiler.TraceAnnotation("bench.lm_decode_submit"):
                t = self.sched.lm_decode(self.arch, self.mesh, self.params,
                                         tok, cache)
        return tokens, times

    def run(self, window: Window) -> Dict[str, float]:
        sched = self.schedule
        n = len(sched)
        due = sched.due_s
        late = np.zeros(n)
        futures: List[Optional[cf.Future]] = [None] * n
        contexts: List[int] = []
        t0 = window.open()
        try:
            for i in range(n):
                target = t0 + due[i]
                with jax.profiler.TraceAnnotation("bench.sleep"):
                    sleep_until(target)
                late[i] = time.perf_counter() - target
                futures[i] = self.pool.submit(
                    self._request, self.prompts[i],
                    int(sched.sizes["output_tokens"][i]), contexts)
            done, _ = cf.wait(futures, timeout=max(
                0.0, window.end + WAIT_S - time.perf_counter()))
        finally:
            window.join()
        tokens: List[Optional[List[int]]] = [None] * n
        ttft, itl, errors = [], [], []
        for i, f in enumerate(futures):
            if f not in done or f.exception() is not None:
                errors.append(f"request {i}: "
                              f"{f.exception() if f in done else 'no answer'}")
                continue
            toks, times = f.result()
            tokens[i] = toks
            ttft.append((times[0] - (t0 + due[i])) * 1e3)
            itl.extend(np.diff(times) * 1e3)
        self.record = {"attempted": n, "failed": n - len(ttft),
                       "errors": errors[:5], "tokens": tokens,
                       "late_ms": (late * 1e3).tolist(),
                       "decode_context": contexts, "ttft_ms": ttft}
        out = {}
        if ttft:
            out["ttft_p90_ms"] = stats.percentile(ttft, 90)
        if itl:
            out["itl_p99_ms"] = stats.percentile(itl, 99)
        return out

    def close(self) -> None:
        if self.pool is not None:
            self.pool.shutdown(wait=True)
            self.pool = None
        if self.sched is not None:
            self.sched.shutdown()
            self.sched = None
        self.svc = None

    # ---- the check ---------------------------------------------------------
    def sample(self) -> List[int]:
        """Finished requests to check: the first with the longest prompt,
        and more drawn from the seed."""
        toks = self.record["tokens"]
        finished = [i for i, t in enumerate(toks) if t is not None]
        if not finished:
            return []
        longest = max(finished, key=lambda i: (len(self.prompts[i]), -i))
        rest = [i for i in finished if i != longest]
        k = min(len(rest), int(self.spec["check_requests"]) - 1)
        pick = self.ctx.rng("check").choice(len(rest), size=k, replace=False)
        return [longest] + [rest[j] for j in sorted(pick)]

    def gaps(self, i: int, control: bool) -> float:
        """Widest gap, over request i's served tokens, between the
        reference's best logit and its logit for the served token (the
        control: for the token its own logits put first)."""
        prompt = self.prompts[i]
        toks = self.record["tokens"][i]
        seq = np.concatenate([prompt, toks[:-1]]).astype(np.int32)
        # one shape for every request (the forward pass is causal, so the
        # padding after the sequence changes none of its logits)
        n = len(seq)
        seq = jnp.asarray(np.pad(seq, (0, int(self.spec["cache_size"]) - n)))
        rows = slice(len(prompt) - 1, n)
        want = np.asarray(ref.forward(self.params, seq, self.cfg)[rows],
                          np.float64)
        if control:
            od = CONTROL_DTYPE[self.cfg["precision"]["mxu_operands"]]
            low = np.asarray(ref.forward(self.params, seq, self.cfg, od)[rows])
            served = np.argmax(low, axis=-1)
        else:
            served = np.asarray(toks)
        if not np.all(np.isfinite(want)):
            return float("inf")
        best = want.max(axis=-1)
        return float(np.max(best - want[np.arange(len(served)), served]))

    def check(self, mode: str = "program") -> List[Comparison]:
        """`mode`: "program" compares the served tokens; "control" the
        tokens the reference, in the precision below the configuration's,
        puts first."""
        ids = self.sample()
        worst = max((self.gaps(i, mode == "control") for i in ids),
                    default=float("inf"))
        lim = float(self.cfg["limits"]["lm_chat"]["gap_logits"])
        return [Comparison("gap_logits", worst, lim)]
