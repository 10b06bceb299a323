from .engine import (EngineBase, ServeConfig, build_prefill_step,
                     build_decode_step, greedy_mismatches)
from .continuous import ContinuousServingEngine
from .stats import Request, RequestMetrics, ServeStats, as_requests


def make_engine(scheduler: str, model, params,
                cfg: ServeConfig) -> ContinuousServingEngine:
    """The serving engine; ``scheduler`` must be ``"continuous"``."""
    if scheduler != "continuous":
        raise ValueError(f"unknown scheduler {scheduler!r}; the only one "
                         f"is 'continuous'")
    return ContinuousServingEngine(model, params, cfg)


__all__ = ["ServeConfig", "ContinuousServingEngine", "EngineBase",
           "Request", "RequestMetrics", "ServeStats", "as_requests",
           "make_engine", "build_prefill_step", "build_decode_step",
           "greedy_mismatches"]
