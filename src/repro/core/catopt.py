"""CATopt: catastrophe-bond basis-risk minimisation (the paper's flagship
co-operative-parallel workload).

Problem (paper §4): a cat bond with a parametric trigger pays
    Recovery_i(w) = min(max(sum_j w_j * IL_{i,j} - Att, 0), Limit)
for event i, where IL are industry losses by region-peril and w are the
sponsor's market-share weights (2000-4000 dims).  The sponsor wants weights
minimising *basis risk* — the mismatch between the parametric recovery and
the recovery its actual losses cl_i would have warranted.

Solver: a distributed genetic algorithm in the style of rgenoud (the R
package the paper uses): population-based evolutionary search with several
mutation/crossover operators plus a derivative-based polish of the elite
(rgenoud's BFGS step, here a batched Adam polish — the TPU-native
vectorised equivalent; see DESIGN.md §2).

Distribution: island model.  Each device (over the mesh's flat device list)
evolves an independent sub-population; every ``migrate_every`` generations
the islands' best individuals migrate around a ring via
``jax.lax.ppermute`` — the cooperative step that needs interconnect, and
the reason this workload measures communication overhead (paper Fig. 4).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# ---------------------------------------------------------------------------
# Problem
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CatBondProblem:
    industry_losses: jnp.ndarray   # (E, m)
    target_recovery: jnp.ndarray   # (E,) recovery the actual losses warranted
    attachment: float
    limit: float
    weight_budget: float           # sum(w) <= budget (market-share constraint)

    @property
    def n_events(self) -> int:
        return self.industry_losses.shape[0]

    @property
    def n_dims(self) -> int:
        return self.industry_losses.shape[1]


def make_problem(key, n_events: int = 8192, n_dims: int = 2048,
                 sparsity: float = 0.05, noise: float = 0.05,
                 ) -> CatBondProblem:
    """Synthetic but realistically-shaped CATopt instance.

    Industry losses: lognormal severities on a sparse event-footprint
    (events hit ~sparsity of region-perils).  Actual sponsor losses follow
    a hidden true weight vector + idiosyncratic noise, so a good w exists
    but is not exactly recoverable — i.e. basis risk is reducible, not
    removable, as in the real problem.
    """
    k1, k2, k3, k4 = jax.random.split(key, 4)
    footprint = jax.random.bernoulli(k1, sparsity, (n_events, n_dims))
    severity = jnp.exp(jax.random.normal(k2, (n_events, n_dims)) * 1.5)
    il = jnp.where(footprint, severity, 0.0).astype(jnp.float32)

    w_true = jnp.where(
        jax.random.bernoulli(k3, 0.3, (n_dims,)),
        jax.random.uniform(k3, (n_dims,)), 0.0)
    actual = il @ w_true
    actual = actual * (1 + noise * jax.random.normal(k4, actual.shape))
    att = float(jnp.percentile(actual, 80.0))
    limit = float(jnp.percentile(actual, 99.0) - att)
    target = jnp.clip(actual - att, 0.0, limit).astype(jnp.float32)
    return CatBondProblem(industry_losses=il, target_recovery=target,
                          attachment=att, limit=limit,
                          weight_budget=float(jnp.sum(w_true)) * 1.5)


# ---------------------------------------------------------------------------
# Objective
# ---------------------------------------------------------------------------

def recovery(problem: CatBondProblem, w: jnp.ndarray) -> jnp.ndarray:
    """w: (..., m) -> (..., E) parametric recovery per event."""
    from repro.kernels import recovery_ops
    return recovery_ops.recovery(problem.industry_losses, w,
                                 problem.attachment, problem.limit)


def basis_risk(problem: CatBondProblem, w: jnp.ndarray) -> jnp.ndarray:
    """RMSE basis risk + constraint penalties.  w: (..., m) -> (...)."""
    from repro.kernels import recovery_ops
    return recovery_ops.basis_risk(
        problem.industry_losses, problem.target_recovery, w,
        problem.attachment, problem.limit, problem.weight_budget)


# ---------------------------------------------------------------------------
# GA state & operators (rgenoud-style)
# ---------------------------------------------------------------------------

@dataclass
class GAConfig:
    pop_size: int = 200           # paper: population 200
    generations: int = 50         # paper: 50 generations
    elite: int = 8
    tournament: int = 4
    p_crossover: float = 0.6
    p_mutate: float = 0.25
    polish_k: int = 4             # elites polished with gradients
    polish_steps: int = 5
    polish_lr: float = 0.02
    migrate_every: int = 5
    migrate_k: int = 4            # individuals migrating per ring step
    rgenoud_operators: bool = True  # use rgenoud's 8-operator mix
    nonuniform_b: float = 3.0     # rgenoud's B (mutation decay shape)


# ---------------------------------------------------------------------------
# rgenoud operator set (Mebane & Sekhon 2011) — vectorised.
#
# The paper's CATopt script uses the rgenoud package; its search is driven
# by 9 genetic operators.  Operators 1-8 are implemented below on [0,1]^m
# boxes (operator 9, local-minimum crossover, is subsumed by the batched
# gradient polish which plays rgenoud's derivative role here).  Each child
# is produced by one operator, chosen with rgenoud's default-ish weights.
# ---------------------------------------------------------------------------

def _rgenoud_children(keys, pop, fitness, cfg: GAConfig, gen_frac):
    """pop: (P, m) in [0,1].  gen_frac: g/G in [0,1] (non-uniform decay).

    Returns (P, m) children."""
    P_, m = pop.shape
    k_sel_a, k_sel_b, k_op, k_u1, k_u2, k_u3, k_coord = keys

    pa_idx = _tournament_select(k_sel_a, fitness, P_, cfg.tournament)
    pb_idx = _tournament_select(k_sel_b, fitness, P_, cfg.tournament)
    pa, pb = pop[pa_idx], pop[pb_idx]
    fa, fb = fitness[pa_idx], fitness[pb_idx]

    u = jax.random.uniform(k_u1, (P_, m))
    u2 = jax.random.uniform(k_u2, (P_, m))
    uu = jax.random.uniform(k_u3, (P_, 1))
    coord = jax.nn.one_hot(
        jax.random.randint(k_coord, (P_,), 0, m), m, dtype=pop.dtype)

    # 1 cloning
    c1 = pa
    # 2 uniform mutation (one coordinate -> uniform)
    c2 = pa * (1 - coord) + coord * u
    # 3 boundary mutation (one coordinate -> 0 or 1)
    c3 = pa * (1 - coord) + coord * jnp.round(u)
    # 4 non-uniform mutation (one coordinate, decaying step)
    decay = (1.0 - gen_frac) ** cfg.nonuniform_b
    step = (1.0 - u ** decay)
    up = jnp.where(u2 < 0.5, pa + (1 - pa) * step, pa - pa * step)
    c4 = pa * (1 - coord) + coord * up
    # 5 polytope crossover (convex combination of two parents)
    c5 = uu * pa + (1 - uu) * pb
    # 6 simple (single-point) crossover
    split = jax.random.randint(k_op, (P_, 1), 1, m)
    left = jnp.arange(m)[None, :] < split
    c6 = jnp.where(left, pa, pb)
    # 7 whole non-uniform mutation (all coordinates)
    c7 = jnp.where(u2 < 0.5, pa + (1 - pa) * step, pa - pa * step)
    # 8 heuristic crossover: child = better + u*(better - worse)
    better = jnp.where((fa < fb)[:, None], pa, pb)
    worse = jnp.where((fa < fb)[:, None], pb, pa)
    c8 = better + uu * (better - worse)

    ops = jnp.stack([c1, c2, c3, c4, c5, c6, c7, c8])   # (8, P, m)
    # rgenoud-ish default weights
    w = jnp.array([1.0, 1.0, 1.0, 1.0, 1.5, 1.5, 1.0, 1.5])
    choice = jax.random.categorical(k_op, jnp.log(w), shape=(P_,))
    children = jnp.take_along_axis(
        ops, choice[None, :, None], axis=0)[0]
    return jnp.clip(children, 0.0, 1.0)


def _init_pop(key, pop: int, m: int) -> jnp.ndarray:
    return jax.random.uniform(key, (pop, m), jnp.float32, 0.0, 1.0)


def _tournament_select(key, fitness: jnp.ndarray, n: int, k: int):
    """Lower fitness is better.  Returns n winner indices."""
    pop = fitness.shape[0]
    cand = jax.random.randint(key, (n, k), 0, pop)
    cand_fit = fitness[cand]
    return cand[jnp.arange(n), jnp.argmin(cand_fit, axis=1)]


def _ga_generation(problem_arrays, cfg: GAConfig, carry, key,
                   gen_frac=0.5):
    """One generation on one island.  carry = (pop (P,m), fitness (P,))."""
    il, target, att, limit, budget = problem_arrays
    pop, fitness = carry
    P_, m = pop.shape
    keys = jax.random.split(key, 8)

    if cfg.rgenoud_operators:
        children = _rgenoud_children(tuple(keys[:7]), pop, fitness, cfg,
                                     gen_frac)
    else:
        # --- legacy mix: blend/uniform crossover + 3 mutations --------------
        parents_a = pop[_tournament_select(keys[0], fitness, P_,
                                           cfg.tournament)]
        parents_b = pop[_tournament_select(keys[1], fitness, P_,
                                           cfg.tournament)]
        alpha = jax.random.uniform(keys[2], (P_, 1))
        blend = alpha * parents_a + (1 - alpha) * parents_b
        pick = jax.random.bernoulli(keys[3], 0.5, (P_, m))
        uniform_x = jnp.where(pick, parents_a, parents_b)
        use_blend = jax.random.bernoulli(keys[4], 0.5, (P_, 1))
        children = jnp.where(use_blend, blend, uniform_x)
        do_cross = jax.random.bernoulli(keys[4], cfg.p_crossover, (P_, 1))
        children = jnp.where(do_cross, children, parents_a)
        mut_mask = jax.random.bernoulli(keys[5], cfg.p_mutate / 10.0, (P_, m))
        gauss = children + 0.1 * jax.random.normal(keys[5], (P_, m))
        reset = jax.random.uniform(keys[6], (P_, m))
        bound = jnp.round(reset)
        which = jax.random.randint(keys[7], (P_, 1), 0, 3)
        mutated = jnp.where(which == 0, gauss,
                            jnp.where(which == 1, reset, bound))
        children = jnp.where(mut_mask, mutated, children)
        children = jnp.clip(children, 0.0, 1.0)

    # --- elitism ------------------------------------------------------------
    elite_idx = jnp.argsort(fitness)[:cfg.elite]
    from repro.kernels import recovery_ops
    child_fit = recovery_ops.basis_risk(il, target, children, att, limit,
                                        budget)
    # children replace all but the elite slots
    new_pop = children.at[:cfg.elite].set(pop[elite_idx])
    new_fit = child_fit.at[:cfg.elite].set(fitness[elite_idx])

    # --- derivative polish of top-k (rgenoud's quasi-Newton step) -----------
    def polish(w):
        def obj(w_):
            return recovery_ops.basis_risk(il, target, w_[None], att, limit,
                                           budget)[0]
        def adam_step(carry, _):
            w_, mom = carry
            g = jax.grad(obj)(w_)
            mom = 0.9 * mom + 0.1 * g
            w_ = jnp.clip(w_ - cfg.polish_lr * mom, 0.0, 1.0)
            return (w_, mom), None
        (w, _), _ = lax.scan(adam_step, (w, jnp.zeros_like(w)), None,
                             length=cfg.polish_steps)
        return w
    top_idx = jnp.argsort(new_fit)[:cfg.polish_k]
    polished = jax.vmap(polish)(new_pop[top_idx])
    pol_fit = recovery_ops.basis_risk(il, target, polished, att, limit,
                                      budget)
    better = pol_fit < new_fit[top_idx]
    new_pop = new_pop.at[top_idx].set(
        jnp.where(better[:, None], polished, new_pop[top_idx]))
    new_fit = new_fit.at[top_idx].set(jnp.minimum(pol_fit, new_fit[top_idx]))
    return (new_pop, new_fit), jnp.min(new_fit)


def _migrate_ring(pop, fitness, k: int, axis: str):
    """Send the island's top-k individuals to the next island in the ring."""
    n_islands = lax.psum(1, axis)
    idx = jnp.argsort(fitness)[:k]
    emigrants = pop[idx]
    emigrant_fit = fitness[idx]
    perm = [(i, (i + 1) % n_islands) for i in range(n_islands)]
    immigrants = lax.ppermute(emigrants, axis, perm)
    immigrant_fit = lax.ppermute(emigrant_fit, axis, perm)
    # immigrants replace the island's worst
    worst = jnp.argsort(fitness)[-k:]
    pop = pop.at[worst].set(immigrants)
    fitness = fitness.at[worst].set(immigrant_fit)
    return pop, fitness


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def optimize_island(problem: CatBondProblem, cfg: GAConfig, key,
                    pop0: Optional[jnp.ndarray] = None):
    """Single-island GA (instance / workstation path)."""
    from repro.kernels import recovery_ops
    arrays = (problem.industry_losses, problem.target_recovery,
              jnp.float32(problem.attachment), jnp.float32(problem.limit),
              jnp.float32(problem.weight_budget))
    pop = pop0 if pop0 is not None else _init_pop(key, cfg.pop_size,
                                                  problem.n_dims)
    fit = recovery_ops.basis_risk(arrays[0], arrays[1], pop, arrays[2],
                                  arrays[3], arrays[4])

    def gen(carry, inp):
        k, frac = inp
        return _ga_generation(arrays, cfg, carry, k, gen_frac=frac)

    keys = jax.random.split(key, cfg.generations)
    fracs = jnp.arange(cfg.generations) / max(cfg.generations, 1)
    (pop, fit), best_hist = lax.scan(gen, (pop, fit), (keys, fracs))
    best = jnp.argmin(fit)
    return {"w": pop[best], "fitness": fit[best], "history": best_hist,
            "pop": pop, "pop_fitness": fit}


def optimize_islands(problem: CatBondProblem, cfg: GAConfig, key,
                     mesh: Mesh):
    """Distributed island GA via shard_map over all mesh devices.

    The mesh's devices are flattened into one logical "island" axis; each
    device is an island with cfg.pop_size individuals; ring migration every
    cfg.migrate_every generations over ``lax.ppermute``.
    """
    from jax import shard_map
    devices = mesh.devices.reshape(-1)
    n_islands = int(devices.size)
    island_mesh = Mesh(devices, ("island",))
    arrays = (problem.industry_losses, problem.target_recovery,
              jnp.float32(problem.attachment), jnp.float32(problem.limit),
              jnp.float32(problem.weight_budget))

    n_epochs = max(1, cfg.generations // cfg.migrate_every)

    def island_fn(keys_shard):
        # keys_shard: (1, 2) — this island's base key
        from repro.kernels import recovery_ops
        key = jax.random.fold_in(keys_shard[0], lax.axis_index("island"))
        pop = _init_pop(key, cfg.pop_size, problem.n_dims)
        fit = recovery_ops.basis_risk(arrays[0], arrays[1], pop, arrays[2],
                                      arrays[3], arrays[4])

        def epoch(carry, inp):
            pop, fit = carry
            ekey, efrac = inp
            gkeys = jax.random.split(ekey, cfg.migrate_every)
            gfracs = efrac + jnp.arange(cfg.migrate_every) / max(
                cfg.generations, 1)

            def gen(c, kf):
                k, frac = kf
                return _ga_generation(arrays, cfg, c, k, gen_frac=frac)
            (pop, fit), hist = lax.scan(gen, (pop, fit), (gkeys, gfracs))
            pop, fit = _migrate_ring(pop, fit, cfg.migrate_k, "island")
            return (pop, fit), jnp.min(hist)

        ekeys = jax.random.split(key, n_epochs)
        efracs = jnp.arange(n_epochs) * cfg.migrate_every / max(
            cfg.generations, 1)
        (pop, fit), hist = lax.scan(epoch, (pop, fit), (ekeys, efracs))
        best = jnp.argmin(fit)
        return pop[best][None], fit[best][None], hist[None]

    # one base key, folded with the island index inside the shard
    base = jax.random.split(key, 1)[0]
    keys = jnp.broadcast_to(base[None], (n_islands, 2))
    fn = shard_map(island_fn, mesh=island_mesh,
                   in_specs=P("island", None),
                   out_specs=(P("island", None), P("island"),
                              P("island", None)),
                   check_vma=False)
    with island_mesh:
        w_all, fit_all, hist_all = jax.jit(fn)(keys)
    best_island = int(np.argmin(np.asarray(fit_all)))
    return {"w": np.asarray(w_all)[best_island],
            "fitness": float(np.asarray(fit_all)[best_island]),
            "history": np.asarray(hist_all),
            "n_islands": n_islands}
