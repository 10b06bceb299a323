"""MoE window: the program's expert layer, once for each layer of the
stage, on routed prompt tokens.

Set-up resolves the grouped kernel's blocks for the layer's two
classes, gate and up (N = expert width, K = hidden) and down, as the
program does at trace time (``models.layers.moe_gmm_configs``, through
``kernels.autotune.resolve_gmm_config``; the program's own trace then
finds them in the tuner's cache); makes each layer's router and experts (its own
buffers) and its own slab of ``batch x prefill_len`` tokens on the
device from the seed; and compiles the pass.  A pass is one jitted
program, ``bench_moe``: ``models.layers.moe_dropless`` (route, sort,
grouped gate/up, SiLU-GLU, grouped down, unsort, combine) for every
layer, so the routing and the dispatch are timed with the experts.

Tokens: the mix's ``topics`` centroids (unit RMS), one per sequence
drawn under Zipf weights of exponent ``topic_zipf``; a token is its
sequence's centroid plus Gaussian noise of scale ``noise``.  Tokens of
one topic favour the same experts, as those of one language or file do.

The window dispatches passes (``in_flight`` at once) until ``--seconds``
have gone.  Afterwards ``CHECK_LAYERS`` seeded layers of the last pass
are checked against the float32 expert layer of ``bench/reference``,
routed by the reference itself; the tokens whose top-k set differs from
the program's are counted (``route_mismatch``).

With ``--trace 1``, after the window: XLA's ``jax.lax.ragged_dot`` on
the same sorted rows, weights and group sizes (``bench_xla_ragged``,
``XLA_PASSES`` passes, under a trace of its own) for ``gmm_over_xla``;
and ``SCOPE_PASSES`` more passes under a trace of their own, whose device
ops are sorted by the ``jax.named_scope`` of their HLO instruction, for
``moe_dispatch_share``.
"""

from __future__ import annotations

import collections
import functools
import importlib
import re
import shutil
import tempfile
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import generate, moe_counters, trace
from bench.reference import gemm as gemm_ref

XLA_PASSES = 3
SCOPE_PASSES = 3
CHECK_LAYERS = 2
SCOPES = frozenset(("moe.route", "moe.dispatch", "moe.combine"))


def topic_weights(mix: Dict) -> np.ndarray:
    w = 1.0 / np.arange(1, mix["topics"] + 1) ** mix["topic_zipf"]
    return w / w.sum()


def make_tokens(cfg: Dict, mix: Dict, seed: int) -> List:
    """One (batch, prefill_len, hidden) bfloat16 slab per layer."""
    from bench.reference.precision import key_for
    topics = generate.rng_for(seed, "topics").choice(
        mix["topics"], size=mix["batch"], p=topic_weights(mix))
    shape = (cfg["num_hidden_layers"], mix["batch"], mix["prefill_len"],
             cfg["hidden_size"], mix["topics"])
    return list(_tokens(shape, key_for(seed), np.asarray(topics, np.int32),
                        float(mix["noise"])))


@functools.partial(jax.jit, static_argnums=0)
def _tokens(shape, key, topics, noise):
    L, B, S, D, n = shape
    c = jax.random.normal(jax.random.fold_in(key, 0), (n, D), jnp.float32)
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True))
    out = []
    for li in range(L):
        z = jax.random.normal(jax.random.fold_in(key, 1 + li), (B, S, D),
                              jnp.float32)
        out.append((c[topics][:, None, :] + noise * z).astype(jnp.bfloat16))
    return tuple(out)


def _pass(mcfg):
    """Every layer's expert layer as one program, named ``bench_moe``."""
    from repro.models import layers

    def bench_moe(params, slabs):
        return [layers.moe_dropless(p, mcfg, x)
                for p, x in zip(params, slabs)]
    return bench_moe


def _routes(mcfg):
    """Each layer's experts (T, k) and group sizes (E,), by the program's
    own router."""
    from repro.models import layers

    def routes(params, slabs):
        out = []
        for p, x in zip(params, slabs):
            xt = x.reshape(-1, x.shape[-1])
            _, experts = layers.moe_route(p, mcfg, xt)
            out.append((experts, jnp.bincount(experts.reshape(-1),
                                              length=mcfg.moe_experts)))
        return out
    return routes


def _sorted_operands(mcfg, blocks):
    """A layer's sorted rows, SiLU-GLU activations and group sizes, as
    the program's expert layer makes them."""
    from repro.kernels import ops
    from repro.models import layers

    def prep(p, x):
        xt = x.reshape(-1, x.shape[-1])
        _, experts = layers.moe_route(p, mcfg, xt)
        _, gs, rows = layers.moe_dispatch(xt, experts, mcfg.moe_experts)
        g = ops.gmm_op(rows, p["w_gate"], gs, blocks[0])
        u = ops.gmm_op(rows, p["w_up"], gs, blocks[0])
        h = (jax.nn.silu(g.astype(jnp.float32))
             * u.astype(jnp.float32)).astype(x.dtype)
        return rows, h, gs
    return prep


def bench_xla_ragged(rows, h, gs, p):
    """One layer's grouped products through XLA's own ``ragged_dot``."""
    def dot(a, w):
        return jax.lax.ragged_dot(a, w, gs, preferred_element_type=jnp.float32
                                  ).astype(a.dtype)
    return dot(rows, p["w_gate"]), dot(rows, p["w_up"]), dot(h, p["w_down"])


OP_NAME = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = .*?op_name="([^"]*)"')


def scoped_instructions(hlo_text: str) -> frozenset:
    """HLO instructions whose ``op_name`` lies under one of ``SCOPES``."""
    out = set()
    for line in hlo_text.splitlines():
        m = OP_NAME.match(line)
        if m and SCOPES & set(m.group(2).split("/")):
            out.add(m.group(1))
    return frozenset(out)


def scope_times(path: str, scoped: frozenset, module: str
                ) -> Tuple[float, float]:
    """(device seconds of the ops in ``scoped``, device seconds of
    ``module``) of a profiler trace, over its TPU planes."""
    from jax.profiler import ProfileData
    ops_s = module_s = 0.0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Modules":
                module_s += sum(e.duration_ns for e in line.events
                                if trace.module_name(e.name) == module)
            elif line.name == "XLA Ops":
                ops_s += sum(e.duration_ns for e in line.events
                             if e.name.split(" = ", 1)[0].lstrip("%")
                             in scoped)
    return ops_s / 1e9, module_s / 1e9


def run(r) -> None:
    # the kernels' import (Pallas) is not the tuner's time
    import repro.kernels.ops  # noqa: F401
    from repro.models import layers

    cfg, mix = r.config, r.traffic
    system = importlib.import_module(f"bench.systems.{cfg['system']}")
    ref = importlib.import_module(f"bench.reference.{cfg['system']}")
    mcfg = system.program_config(cfg)
    L, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    T, D = mix["batch"] * mix["prefill_len"], cfg["hidden_size"]
    t = time.perf_counter()
    blocks = layers.moe_gmm_configs(mcfg, T * k, 2)
    tune_s = time.perf_counter() - t
    d = ref.dims(cfg)
    params = [system.moe_params(ref.make_layer(d, ref.layer_key(r.seed, li)))
              for li in range(L)]
    slabs = make_tokens(cfg, mix, r.seed)
    compiled = jax.jit(_pass(mcfg)).lower(params, slabs).compile()
    jax.block_until_ready(compiled(params, slabs))
    routes = jax.jit(_routes(mcfg))(params, slabs)
    loads = np.asarray([np.asarray(gs) for _, gs in routes])
    check = sorted(generate.rng_for(r.seed, "check").choice(
        L, CHECK_LAYERS, replace=False).tolist())
    experts = {li: routes[li][0] for li in check}
    del routes
    r.setup_done()

    in_flight = mix.get("in_flight", 1)
    queue: collections.deque = collections.deque()
    kept: Dict[int, object] = {}

    def finish(outs) -> None:
        jax.block_until_ready(outs)
        kept.clear()
        kept.update({li: outs[li] for li in check})

    passes = 0
    r.open_window()
    while True:
        queue.append(compiled(params, slabs))
        if len(queue) < in_flight:
            continue
        finish(queue.popleft())
        passes += 1
        if r.elapsed() >= r.seconds:
            break
    while queue:
        finish(queue.popleft())
        passes += 1
    r.close_window()

    flops = L * moe_counters.layer_flops(cfg, T)
    calls = [c for sizes in loads.tolist()
             for c in moe_counters.layer_calls(cfg, sizes)]
    ratio = loads.max(1) / loads.mean(1)
    r.attempted, r.failed = passes * L, 0
    r.e2e["gemm_tflops"] = passes * flops / r.window_s / 1e12
    r.counts.update(
        tune_s=tune_s, passes=passes, pass_flops=flops,
        gmm_min_s=moe_counters.min_seconds(calls, r.peak),
        loads=loads.tolist(), load_max_over_mean=ratio.tolist(),
        load_max_over_mean_median=float(np.median(ratio)),
        blocks=[[b.bm, b.bk, b.bn] for b in blocks])

    if r.trace:
        prep = jax.jit(_sorted_operands(mcfg, blocks))
        xla = jax.jit(bench_xla_ragged)
        jax.block_until_ready(xla(*prep(params[0], slabs[0]), params[0]))

        def xla_passes():
            for _ in range(XLA_PASSES):
                for p, x in zip(params, slabs):
                    jax.block_until_ready(xla(*prep(p, x), p))
        r.traced("xla_ragged", xla_passes)
        r.counts["xla_passes"] = XLA_PASSES

        scoped = scoped_instructions(compiled.as_text())
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        trace.start(tdir)
        for _ in range(SCOPE_PASSES):
            jax.block_until_ready(compiled(params, slabs))
        r.extra["moe_scopes"] = scope_times(trace.stop(tdir), scoped,
                                            "jit_bench_moe")
        shutil.rmtree(tdir, ignore_errors=True)

    err, mismatch = 0.0, 0
    program = 0.0
    for li in check:
        w = params[li]
        x = slabs[li].reshape(T, D)
        want, ref_experts = ref.moe_block(x, w, k)
        got = kept[li].reshape(T, D)
        program = max(program, float(gemm_ref.row_rel_err(got, want)))
        if r.control:
            got, _ = ref.moe_block(x, w, k, r.control)
        err = max(err, float(gemm_ref.row_rel_err(got, want)))
        mismatch += int(np.sum(np.any(
            np.sort(np.asarray(ref_experts), 1)
            != np.sort(np.asarray(experts[li]), 1), axis=1)))
    r.counts["route_mismatch"] = mismatch
    if r.control:
        # the control stands in the program's place and is judged alike
        r.counts["program"] = {"row_err": program}
    r.check("row_err", err, cfg["limits"]["row_err"])
