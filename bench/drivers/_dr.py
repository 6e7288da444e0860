"""The DR service a configuration file describes, with its state made here
from the seed, and the reference's outputs for the checks."""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import waveform
from bench.drivers import Comparison, Context
from bench.reference import dr as ref

NAME = "dr"
CONTROL_DTYPE = {"bfloat16": jnp.float8_e4m3fn}


def build_model(cfg: Dict[str, Any]):
    """The program's `DRModel` for the configuration: RP m -> p, then an
    EASI stage p -> n with the configured personality, under the
    configured execution backend."""
    from repro.core.execution import Execution
    from repro.dr import DRModel, EASIStage, RPStage

    e = cfg["easi"]
    stages = (RPStage(cfg["m"], cfg["p"], sparsity=cfg["rp"]["sparsity"],
                      normalize=cfg["rp"]["normalize"]),
              EASIStage(m=cfg["p"], n=cfg["n"], mu=e["mu"], g=e["g"],
                        second_order=e["second_order"],
                        higher_order=e["higher_order"]))
    return DRModel(stages=stages, block_size=cfg["block_size"],
                   execution=Execution(**cfg["execution"]))


def control_dtype(cfg: Dict[str, Any]):
    """The type one step below the operands the configuration states."""
    return CONTROL_DTYPE[cfg["precision"]["mxu_operands"]]


class DRSystem:
    """A `DRService` with the configuration's model registered under
    `NAME`, its state made by the reference's init from the seed."""

    def __init__(self, ctx: Context):
        from repro.serve import BucketPolicy, DRService

        self.ctx = ctx
        cfg = self.cfg = ctx.config
        self.scale = ref.rp_scale(cfg)
        self.r, self.b0 = ref.init_state(cfg, ctx.jax_key("weights"))
        self.model = build_model(cfg)
        treedef = jax.tree.structure(
            jax.eval_shape(self.model.init, jax.random.PRNGKey(0)))
        self.state0 = jax.tree.unflatten(
            treedef, [self.r, self.b0, jnp.zeros((), jnp.int32)])
        self.pool = waveform.pool(ctx.rng("payload"),
                                  cfg["data"]["pool_rows"], cfg["m"])
        self.svc = DRService(buckets=BucketPolicy(**cfg["buckets"]))

    def register(self, name: str = NAME) -> None:
        self.svc.register(name, self.model, self.state0)

    def counters(self) -> Dict[str, Any]:
        """The service's row and batch counters, its stages' histograms
        (`stages`) and its SLO cells' (`slo`)."""
        m = self.svc.metrics()
        out = {k: m[k] for k in ("served_rows", "padded_rows",
                                 "batches_run", "host_batches", "stages")}
        out["slo"] = self.svc.slo.snapshot()
        return out

    def free(self) -> None:
        """Drop the service and the program's copy of the state."""
        self.svc = None
        self.state0 = None

    def limit(self, driver: str, name: str) -> float:
        return float(self.cfg["limits"][driver][name])


def rel_gap(got: np.ndarray, want: np.ndarray, scale: float) -> float:
    """max |got - want| over `scale` (the reference output's own size)."""
    if got.shape != want.shape:
        return float("inf")
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.max(np.abs(got.astype(np.float64) - want)) / scale)


def served_out_check(name: str, limit: float, answers, offsets, rows,
                     want_pool: np.ndarray) -> Comparison:
    """Every answer against the reference rows it was cut from, as one
    number: the widest gap over the output's scale."""
    scale = float(np.max(np.abs(want_pool)))
    worst = 0.0
    for y, off, n in zip(answers, offsets, rows):
        if y is None:
            continue
        worst = max(worst, rel_gap(y, want_pool[off:off + n], scale))
    return Comparison(name, worst, limit)


def reference_pool(system: DRSystem, b: Any, operand_dtype: Optional[Any]):
    """The reference's outputs for every pool row, float64 on the host;
    under "highest" precision, in blocks so that it fits."""
    out = []
    f = jax.jit(lambda r, b, x: ref.transform(r, b, x, system.scale,
                                              operand_dtype))
    step = 65536
    for i in range(0, system.pool.shape[0], step):
        out.append(np.asarray(f(system.r, b, jnp.asarray(
            system.pool[i:i + step])), np.float64))
    return np.concatenate(out)
