"""Operations and bytes a fit unit needs, from shapes and solver iteration
counts. Kept with the benchmark so that no PR that claims a gain can change
how its gain is counted.

One value-and-gradient of a GLM objective on an [n, d] design matrix needs
4 n d floating-point operations (margin mat-vec 2nd, gradient mat-vec 2nd) and
at least ONE read of the matrix, n d itemsize bytes, whatever implements it
(the stock lowering reads it twice; a fused kernel once). A solve of ``it``
iterations makes at least ``it + 1`` such evaluations: one per iteration plus
the initial one. Line-search re-evaluations are not counted (the program has
no counter for them), so both are floors: the shares computed from them are
conservative and cannot pass 100 %.
"""

from __future__ import annotations


def fe_solve_flops(n: int, d: int, iterations: float) -> float:
    return (iterations + 1.0) * 4.0 * n * d


def fe_solve_bytes(n: int, d: int, iterations: float, itemsize: int = 4) -> float:
    return (iterations + 1.0) * n * d * itemsize


def re_solve_flops(n: int, k: int, iterations_mean: float) -> float:
    """Each entity's solve evaluates its own rows: summed over entities that
    is n rows at the mean iteration count (lanes idling until the slowest
    finishes do no needed work)."""
    return (iterations_mean + 1.0) * 4.0 * n * k


def unit_flops(cfg: dict, iterations: list) -> float:
    """FLOPs one fit unit needs. ``iterations`` is one ``{coordinate id:
    [count per update]}`` per model of the sweep, as ``entry.unit_answers``
    gives it."""
    n = int(cfg["n_train_rows"])
    total = 0.0
    for model in iterations:
        for c in cfg["coordinates"]:
            for it in model[c["id"]]:
                if c["kind"] == "fixed":
                    total += fe_solve_flops(n, int(cfg["fixed_effect_dim"]), it)
                else:
                    total += re_solve_flops(n, int(cfg["random_effect_dim"]), it)
    return total


def unit_fe_bytes(cfg: dict, iterations: list) -> float:
    n, d = int(cfg["n_train_rows"]), int(cfg["fixed_effect_dim"])
    return sum(
        fe_solve_bytes(n, d, it)
        for model in iterations
        for c in cfg["coordinates"]
        if c["kind"] == "fixed"
        for it in model[c["id"]]
    )
