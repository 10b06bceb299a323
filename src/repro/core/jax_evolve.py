"""JAX-compiled evolution: the whole generation loop as one XLA program.

This is the third search engine (DESIGN.md §3).  The PR 5 NumPy SoA
engine made populations ``[B, L, 3]`` matrices but still runs ~10 NumPy
dispatches plus the scalar Mersenne draws per generation on the host;
here selection, crossover, mutation, legalization and fitness are all
array ops inside a single jitted ``lax.scan`` over generations, and a
``chains=`` axis is one extra ``vmap`` — multi-chain (island-model)
evolution and multi-chain SA cost barely more than one chain because the
whole run is a single dispatch.

RNG-stream mapping (documented contract — the point where this engine
*departs* from the NumPy oracle).  The SoA engine replays CPython's
Mersenne ``getrandbits`` stream draw-for-draw; that stream is inherently
sequential (rejection sampling consumes a data-dependent number of
draws), so a compiled engine cannot replicate it.  Instead each scalar
draw maps to a ``jax.random`` (threefry) draw of fixed shape:

  ============================  =====================================
  NumPy SoA draw                JAX draw
  ============================  =====================================
  selection coin rr()<rate      uniform[C] < rate
  parent pair sample(range(P))  j1=randint[C](0,P); j2=randint[C](0,P-1),
                                j2==j1 -> P-1   (CPython's k=2 pool trick:
                                uniform over distinct ordered pairs)
  per-loop coin rr()<0.5        uniform[C,L] < 0.5 (True -> first parent)
  mutation loop choice          randint[C](0,L)
  level pair sample(range(3),2) a=randint(0,3); b=randint(0,2), b==a -> 2
  hybrid coin rr()<alpha        uniform[C] < alpha (divisors_only: always)
  divisor choice(divs(va))      floor(uniform*nd) into a padded divisor
                                table (va with no divisor>1: f=1, a no-op,
                                like the scalar path's skipped mutation)
  random s=randint(1,va)        1 + floor(uniform*va)
  ============================  =====================================

Both streams are deterministic at a fixed seed, and the *search
distribution* is identical (every draw is uniform over the same set, up
to the <=2^-53 float-index bias of ``floor(u*n)``); only the realized
trajectories differ.  Equivalence to the oracle is therefore asserted at
the level that matters: on the reference searches both engines converge
to the same best genome and latency (``tests/test_batch_equivalence.py``),
and the fitness function itself matches to ``rtol=1e-12`` (``jax_model``).
Unlike the dedup'd NumPy engine, the compiled loop re-evaluates the full
population every generation (dedup is a host-side hash structure), so
``evals`` reports ``chains * population * (epochs_run + 1)`` — the count
actually computed.

Dtype policy: every entry point runs under ``jax.enable_x64``
(see ``jax_model``); genomes stay int64 end-to-end, divisions that must
round (tile counts, the random-mutation compensation ``ceil(va*vb/s)``)
go through float64 exactly like the NumPy legalizer.

Fork constraint: this module imports ``jax`` at module scope and must
only ever be imported lazily (``SoaHandle.jax_ops()`` /
``evolve(..., engine="jax")``) so ``core.engine``'s jax-free fork fast
path survives (``SearchSession._fork_safe``).
"""

from __future__ import annotations

import time
from typing import List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import enable_x64

from .design_space import (_divisors_gt1, _divisors_t, _pow2_floor,
                           _simd_opts, _snap_tables, genome_from_row,
                           genomes_to_matrix)
from .evolutionary import EvoConfig, EvoResult, TraceEntry
from .jax_model import build_fitness_fn
from repro.obs import get_tracer

__all__ = ["JaxEngineOps", "evolve_jax", "simulated_annealing_jax"]

_I8 = np.int64


def _pow2_floor_j(x):
    """jnp port of ``design_space._pow2_floor_arr`` (uint64 bit smear)."""
    x = x.astype(jnp.uint64)
    for s in (1, 2, 4, 8, 16, 32):
        x = x | (x >> s)
    return ((x >> 1) + 1).astype(jnp.int64)


def _fidx(u, n):
    """floor(u*n) clamped into [0, n-1] — the uniform-index draw."""
    return jnp.minimum((u * n).astype(jnp.int64), jnp.maximum(n - 1, 0))


class JaxEngineOps:
    """Compiled genome operators for one (space, batch model) pair.

    Everything data-independent — loop bounds, divisor tables, snap
    tables, the fitness pipeline's static structure — is baked into the
    traced functions as constants; compiled executables are cached per
    population/chain shape on this object (which ``SoaHandle.jax_ops()``
    in turn caches on the batch model), so repeated ``evolve`` calls at
    the same config pay zero retrace.
    """

    def __init__(self, space, batch_model, use_max_model: bool = False):
        self.space = space
        self.batch_model = batch_model
        self.use_max_model = bool(use_max_model)
        wl = space.wl
        self.names = list(wl.loop_names)
        self.L = len(self.names)
        self.div_only = bool(space.divisors_only)
        self.simd_max = wl.simd_max
        self.loops = []
        for l in wl.loops:
            is_simd = l.name == wl.simd_loop
            self.loops.append({
                "bound": l.bound,
                "lvl2": space.has_level2(l.name),
                "is_simd": is_simd,
                # the n2-alone-over-bound clamp value (static per loop)
                "shrunk": (min(_pow2_floor(max(1, l.bound)), wl.simd_max)
                           if is_simd else max(1, l.bound)),
                "snap": _snap_tables(l.bound) if self.div_only else None,
                "divs": np.asarray(_divisors_t(l.bound), dtype=_I8),
            })
        # global divisor tables over every level value that can occur
        # (legalized levels are <= max bound), padded with 1 so a value
        # without divisors > 1 turns the factorization move into a no-op
        maxb = max(lp["bound"] for lp in self.loops)
        gt1 = [_divisors_gt1(v) for v in range(maxb + 1)]
        alld = [_divisors_t(v) for v in range(maxb + 1)]
        self._nd_gt1 = np.array([len(d) for d in gt1], dtype=_I8)
        self._dt_gt1 = np.ones(
            (maxb + 1, max(1, max(len(d) for d in gt1))), dtype=_I8)
        for v, ds in enumerate(gt1):
            self._dt_gt1[v, :len(ds)] = ds
        self._nd_all = np.array([len(d) for d in alld], dtype=_I8)
        self._dt_all = np.ones(
            (maxb + 1, max(1, max(len(d) for d in alld))), dtype=_I8)
        for v, ds in enumerate(alld):
            self._dt_all[v, :len(ds)] = ds
        self._scnt = np.array(
            [len(_simd_opts(min(max(v, 1), wl.simd_max)))
             for v in range(maxb + 1)], dtype=_I8)
        self._fitness = build_fitness_fn(batch_model)
        self._compiled: dict = {}

    # -- traced pieces (must run inside jit under enable_x64) ------------
    def _fit_of(self, pop):
        return self._fitness(pop[:, :, 0], pop[:, :, 1], pop[:, :, 2],
                             self.use_max_model)

    def _legalize(self, mat):
        """jnp port of ``GenomeSpace.legalize_matrix`` (same op order)."""
        n0s, n1s, n2s = [], [], []
        for li, lp in enumerate(self.loops):
            bound = lp["bound"]
            n1 = jnp.maximum(1, mat[:, li, 1])
            n2 = jnp.maximum(1, mat[:, li, 2])
            if not lp["lvl2"]:
                n1 = n1 * n2
                n2 = jnp.ones_like(n2)
            if lp["is_simd"]:
                n2 = jnp.minimum(_pow2_floor_j(n2), self.simd_max)
            over = n1 * n2 > bound
            n1 = jnp.where(over, jnp.maximum(1, bound // n2), n1)
            over = n1 * n2 > bound
            n2 = jnp.where(over, lp["shrunk"], n2)
            n1 = jnp.where(over, 1, n1)
            if self.div_only:
                M, DI, T = (jnp.asarray(t) for t in lp["snap"])
                t1 = M[n1 * n2]
                n2 = T[DI[t1], jnp.minimum(n2, bound)]
                n1 = t1 // n2
            n0s.append(jnp.maximum(
                1, jnp.ceil(bound / (n1 * n2))).astype(jnp.int64))
            n1s.append(n1)
            n2s.append(n2)
        return jnp.stack([jnp.stack(n0s, 1), jnp.stack(n1s, 1),
                          jnp.stack(n2s, 1)], axis=2)

    def _sample(self, key, n: int):
        """jnp port of ``GenomeSpace.sample_matrix`` (same distribution)."""
        k1, k2 = jax.random.split(key)
        u1 = jax.random.uniform(k1, (n, self.L))
        u2 = jax.random.uniform(k2, (n, self.L))
        nd_all = jnp.asarray(self._nd_all)
        dt_all = jnp.asarray(self._dt_all)
        scnt = jnp.asarray(self._scnt)
        n1s, n2s = [], []
        for li, lp in enumerate(self.loops):
            bound = lp["bound"]
            if self.div_only:
                divs = jnp.asarray(lp["divs"])
                t1 = divs[_fidx(u1[:, li], len(lp["divs"]))]
            else:
                t1 = 1 + _fidx(u1[:, li], bound)     # randint(1, bound)
            if lp["lvl2"] and lp["is_simd"]:
                n2 = jnp.left_shift(jnp.asarray(1, jnp.int64),
                                    _fidx(u2[:, li], scnt[t1]))
                n1 = jnp.maximum(1, t1 // n2)
            elif lp["lvl2"]:
                n2 = dt_all[t1, _fidx(u2[:, li], nd_all[t1])]
                n1 = t1 // n2
            else:
                n1, n2 = t1, jnp.ones_like(t1)
            n1s.append(n1)
            n2s.append(n2)
        mat = jnp.stack([jnp.ones((n, self.L), jnp.int64),
                         jnp.stack(n1s, 1), jnp.stack(n2s, 1)], axis=2)
        return self._legalize(mat)

    def _mutate_rows(self, key, mat, alpha: float):
        """Raw hybrid mutation of every row (``soa_mutate_rows`` port)."""
        R = mat.shape[0]
        kli, ka, kb, kf, kfi, ks = jax.random.split(key, 6)
        rows = jnp.arange(R)
        li = jax.random.randint(kli, (R,), 0, self.L)
        a = jax.random.randint(ka, (R,), 0, 3)
        b = jax.random.randint(kb, (R,), 0, 2)
        b = jnp.where(b == a, 2, b)                 # sample(range(3), 2)
        if self.div_only:
            fact = jnp.ones((R,), bool)
        else:
            fact = jax.random.uniform(kf, (R,)) < alpha
        lv = mat[rows, li]                          # [R, 3]
        va = lv[rows, a]
        vb = lv[rows, b]
        nd = jnp.asarray(self._nd_gt1)[va]
        f = jnp.asarray(self._dt_gt1)[va, _fidx(
            jax.random.uniform(kfi, (R,)), nd)]     # 1 when nd == 0
        s = jnp.minimum(
            1 + (jax.random.uniform(ks, (R,)) * va).astype(jnp.int64), va)
        new_a = jnp.where(fact, va // f, s)
        new_b = jnp.where(fact, vb * f,
                          jnp.ceil(va * vb / s).astype(jnp.int64))
        return mat.at[rows, li, a].set(new_a).at[rows, li, b].set(new_b)

    # -- compiled entry points -------------------------------------------
    def get_runner(self, B: int, P: int, E: int, rate: float, alpha: float):
        """(prep, run) jitted pair for one evolve configuration.

        ``prep(keys[K], seed_mat)`` samples + scores the initial
        populations; ``run(keys, pop, fit, best_f, best_row, nsteps)``
        advances every chain ``nsteps`` generations in one dispatch and
        returns the updated state plus the per-epoch best-fitness trace.
        Both are vmapped over the leading chain axis.
        """
        cache_key = ("evo", B, P, E, rate, alpha)
        hit = self._compiled.get(cache_key)
        if hit is not None:
            return hit
        C = B - E
        do_cross = rate > 0.0 and P >= 2

        def gen(key, pop, fit):
            order = jnp.argsort(-fit, stable=True)
            parents = pop[order[:P]]
            kc, kj1, kj2, kl, km = jax.random.split(key, 5)
            j1 = jax.random.randint(kj1, (C,), 0, P)
            if do_cross:
                cross = jax.random.uniform(kc, (C,)) < rate
                j2 = jax.random.randint(kj2, (C,), 0, max(P - 1, 1))
                j2 = jnp.where(j2 == j1, P - 1, j2)
                src = jnp.where(
                    cross[:, None],
                    jnp.where(jax.random.uniform(kl, (C, self.L)) < 0.5,
                              j1[:, None], j2[:, None]),
                    j1[:, None])
            else:
                src = jnp.broadcast_to(j1[:, None], (C, self.L))
            child = parents[src, jnp.arange(self.L)[None, :]]
            child = self._mutate_rows(km, child, alpha)
            pop = jnp.concatenate([pop[order[:E]], child]) if E else child
            pop = self._legalize(pop)
            return pop, self._fit_of(pop)

        def run(key, pop, fit, best_f, best_row, nsteps):
            def body(carry, _):
                key, pop, fit, best_f, best_row = carry
                key, sub = jax.random.split(key)
                pop, fit = gen(sub, pop, fit)
                i = jnp.argmax(fit)                 # first max, like the
                better = fit[i] > best_f            # stable argsort
                best_f = jnp.where(better, fit[i], best_f)
                best_row = jnp.where(better, pop[i], best_row)
                return (key, pop, fit, best_f, best_row), best_f
            carry = (key, pop, fit, best_f, best_row)
            carry, hist = lax.scan(body, carry, None, length=nsteps)
            return carry + (hist,)

        def prep(key, seed_mat):
            S = seed_mat.shape[0]
            if S >= B:
                pop = seed_mat[:B]
            elif S:
                pop = jnp.concatenate([seed_mat, self._sample(key, B - S)])
            else:
                pop = self._sample(key, B)
            fit = self._fit_of(pop)
            i = jnp.argmax(fit)
            return pop, fit, fit[i], pop[i]

        pair = (jax.jit(jax.vmap(prep, in_axes=(0, None))),
                jax.jit(jax.vmap(run, in_axes=(0, 0, 0, 0, 0, None)),
                        static_argnums=5))
        self._compiled[cache_key] = pair
        return pair

    def get_sa(self, R: int, temperature: float, steps: int, alpha: float):
        """Jitted lockstep-SA advance: ``sa(carry, step_idx[seg])``.

        The ``R`` chains are the batch axis of one state matrix — a
        16-chain step is the same single dispatch as a 1-chain step.
        Matches the NumPy lockstep SA except that the acceptance scale
        ``|best_f|`` is the *previous* step's global best (the NumPy loop
        updates it mid-step, chain by chain — a sequential dependence a
        compiled batch cannot have).
        """
        cache_key = ("sa", R, temperature, steps, alpha)
        hit = self._compiled.get(cache_key)
        if hit is not None:
            return hit

        def step(carry, i):
            key, cur, cur_f, best_f, best_row = carry
            key, km, kacc = jax.random.split(key, 3)
            t = temperature * (1.0 - i / steps) + 1e-6
            cand = self._legalize(self._mutate_rows(km, cur, alpha))
            f = self._fit_of(cand)
            scale = jnp.abs(best_f) + 1e-9
            u = jax.random.uniform(kacc, (R,))
            accept = (f >= cur_f) | \
                (u < jnp.exp((f - cur_f) / scale / t * 1e3))
            cur = jnp.where(accept[:, None, None], cand, cur)
            cur_f = jnp.where(accept, f, cur_f)
            j = jnp.argmax(f)
            better = f[j] > best_f
            best_f = jnp.where(better, f[j], best_f)
            best_row = jnp.where(better, cand[j], best_row)
            return (key, cur, cur_f, best_f, best_row), best_f

        def sa_prep(key):
            cur = self._sample(key, R)
            cur_f = self._fit_of(cur)
            j = jnp.argmax(cur_f)
            return cur, cur_f, cur_f[j], cur[j]

        pair = (jax.jit(sa_prep),
                jax.jit(lambda carry, idx: lax.scan(step, carry, idx)))
        self._compiled[cache_key] = pair
        return pair


# ---------------------------------------------------------------------- #
# Engine drivers (host side)
# ---------------------------------------------------------------------- #
def evolve_jax(ops: JaxEngineOps, cfg: EvoConfig, seeds: Sequence = (),
               stop_fn=None, chains: int = 1) -> EvoResult:
    """``evolve`` through the compiled engine.

    ``chains`` independent populations run in lockstep under one vmap —
    an island model without migration; the result is the best across
    chains (first chain on ties).  ``seeds`` enter every chain's
    population (same rows, like the NumPy engine's seed injection).

    Dispatch is segmented only when it has to be: with a ``stop_fn`` the
    scan length is 1 (the callback is polled every epoch, same contract
    as the NumPy engine); with only a time budget, segments of up to 32
    epochs bound the overshoot; otherwise the whole run is one dispatch.
    """
    K = max(1, int(chains))
    B = cfg.population
    P = max(1, min(cfg.parents, B))
    E = min(cfg.elites, B - 1) if B > 1 else 0
    tr = get_tracer()
    # compile-vs-run provenance: a cold ops cache means the first prep +
    # first run dispatch pay the XLA compile (spans carry cold=True)
    cold = ("evo", B, P, E, cfg.crossover_rate,
            cfg.mutation_alpha) not in ops._compiled
    t0 = time.perf_counter()

    # deterministic eval accounting: every epoch evaluates K*B rows
    per_epoch = K * B
    epochs = cfg.epochs
    if cfg.max_evals is not None:
        done, budget_epochs = per_epoch, 0
        while budget_epochs < cfg.epochs and done < cfg.max_evals:
            budget_epochs += 1
            done += per_epoch
        epochs = budget_epochs

    if stop_fn is not None:
        seg_len = 1
    elif cfg.time_budget_s is not None:
        seg_len = min(32, max(1, epochs))
    else:
        seg_len = max(1, epochs)

    with enable_x64(True):
        prep, run = ops.get_runner(B, P, E, cfg.crossover_rate,
                                   cfg.mutation_alpha)
        keys = jax.random.split(jax.random.PRNGKey(cfg.seed), K)
        seed_mat = (genomes_to_matrix(list(seeds)[:B], ops.names)
                    if seeds else np.zeros((0, ops.L, 3), dtype=_I8))
        with tr.span("evolve.jax.prep", cat="search", chains=K,
                     population=B, cold=cold):
            pop, fit, best_f, best_row = prep(keys, seed_mat)
            if tr.enabled:          # sync only when timing the span
                jax.block_until_ready(fit)
        evals = per_epoch
        trace: List[TraceEntry] = []

        def _best(bf) -> float:
            return float(jnp.max(bf))

        dt = time.perf_counter() - t0
        trace.append(TraceEntry(evals, dt, _best(best_f),
                                evals / max(1e-12, dt)))
        aborted = False
        epoch = 0
        while epoch < epochs:
            if cfg.time_budget_s is not None and \
                    time.perf_counter() - t0 >= cfg.time_budget_s:
                break
            if stop_fn is not None:
                k = int(jnp.argmax(best_f))
                g = genome_from_row(np.asarray(best_row)[k], ops.names)
                if stop_fn(epoch, _best(best_f), g):
                    aborted = True
                    break
            n = min(seg_len, epochs - epoch)
            with tr.span("evolve.jax.run", cat="search", epochs=n,
                         cold=cold and epoch == 0):
                keys, pop, fit, best_f, best_row, hist = run(
                    keys, pop, fit, best_f, best_row, n)
                # per-epoch trace from the scanned best-fitness history;
                # the wall clock is only observable at segment boundaries,
                # so all epochs of a segment share its end timestamp
                hist = np.asarray(hist)             # [K, n]
            dt = time.perf_counter() - t0
            for j in range(n):
                evals += per_epoch
                bf = float(hist[:, j].max())
                trace.append(TraceEntry(evals, dt, bf,
                                        evals / max(1e-12, dt)))
                if tr.enabled:
                    tr.counter("evolve.gen", best=bf, evals=evals,
                               evals_per_sec=evals / max(1e-12, dt))
            epoch += n

        k = int(jnp.argmax(best_f))
        best = genome_from_row(np.asarray(best_row)[k], ops.names)
        return EvoResult(best=best, best_fitness=_best(best_f),
                         evals=evals, seconds=time.perf_counter() - t0,
                         trace=trace, aborted=aborted)


def simulated_annealing_jax(ops: JaxEngineOps, max_evals: int = 3000,
                            temperature: float = 200.0, seed: int = 0,
                            time_budget_s: Optional[float] = None,
                            chains: int = 1, alpha: float = 0.4
                            ) -> EvoResult:
    """Multi-chain SA as one compiled scan (``baselines`` semantics:
    global eval budget across chains, same temperature schedule)."""
    R = max(1, min(chains, max_evals))
    steps = max(0, (max_evals - R) // R) if R > 1 else max_evals
    t0 = time.perf_counter()
    with enable_x64(True):
        sa_prep, sa_run = ops.get_sa(R, temperature, max(1, steps), alpha)
        key = jax.random.PRNGKey(seed)
        key, kinit = jax.random.split(key)
        cur, cur_f, best_f, best_row = sa_prep(kinit)
        carry = (key, cur, cur_f, best_f, best_row)
        evals = R
        trace: List[TraceEntry] = []
        seg_len = min(64, max(1, steps)) if time_budget_s else max(1, steps)
        i = 0
        while i < steps:
            if time_budget_s and time.perf_counter() - t0 >= time_budget_s:
                break
            n = min(seg_len, steps - i)
            carry, hist = sa_run(carry, jnp.arange(i, i + n))
            evals += n * R
            i += n
            trace.append(TraceEntry(evals, time.perf_counter() - t0,
                                    float(np.asarray(hist)[-1])))
        best_f, best_row = carry[3], carry[4]
        best = genome_from_row(np.asarray(best_row), ops.names)
        return EvoResult(best=best, best_fitness=float(best_f),
                         evals=evals, seconds=time.perf_counter() - t0,
                         trace=trace)
