from .engine import (EngineBase, ServeConfig, ServingEngine,
                     build_prefill_step, build_decode_step,
                     greedy_mismatches, model_gemm_shapes)
from .continuous import ContinuousServingEngine
from .stats import Request, RequestMetrics, ServeStats, as_requests

SCHEDULERS = {"wave": ServingEngine, "continuous": ContinuousServingEngine}


def make_engine(scheduler: str, model, params, cfg: ServeConfig,
                tuning=None, tune_evals: int = 800):
    """Engine factory: ``scheduler`` is "wave" or "continuous"."""
    try:
        cls = SCHEDULERS[scheduler]
    except KeyError:
        raise ValueError(f"unknown scheduler {scheduler!r}; "
                         f"choose from {sorted(SCHEDULERS)}") from None
    return cls(model, params, cfg, tuning=tuning, tune_evals=tune_evals)


__all__ = ["ServeConfig", "ServingEngine", "ContinuousServingEngine",
           "EngineBase", "Request", "RequestMetrics", "ServeStats",
           "as_requests", "make_engine", "SCHEDULERS",
           "build_prefill_step", "build_decode_step", "greedy_mismatches",
           "model_gemm_shapes"]
