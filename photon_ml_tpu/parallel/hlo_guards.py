"""Compile-time HLO guards for the multi-chip pass.

Two classes of SPMD regression compile and run bit-identically to the healthy
program and only betray themselves in the per-device module:

- **replication** (the closure-capture trap): every device computes the full
  pass — caught by the block-shape guard (tests/test_parallel.py and
  ``__graft_entry__.dryrun_multichip`` assert ``[N/m]``-row operand blocks);
- **comm blow-up**: a resharding change that starts gathering per-sample or
  per-entity-block tensors across the mesh — the pass still partitions, but
  the wire carries the dataset instead of gradient-sized reductions. The
  guards here catch that the way the shape guard catches replication.

The healthy GLMix pass's collective profile (SURVEY §2.7: samples shard for
the fixed-effect solve — treeAggregate == psum of value+gradient;
entity-sharded random-effect solves are comm-free inside, with only the
padded per-entity coefficient tables and the per-sample score vector
exchanged between coordinates):

- all-reduce payloads are at most gradient-sized ([D] + scalars),
  convergence predicates, or a padded entity coefficient table ([E_pad, K] —
  per-device scatter updates of entity-sharded solves combine by psum);
- all-gathers materialize only entity coefficient tables ([E_pad, K]) and
  per-sample score vectors ([N]) — never the design matrix or RE bucket
  blocks;
- no all-to-all / reduce-scatter / collective-permute at all today, so any
  appearance is a deliberate-change signal, not noise.

The mesh-sharded single-program coordinate update (PR 10) adds a third,
sharper guard: the RE bucket SOLVES — everything inside the optimizer
``while`` loops — are embarrassingly parallel across entity shards and must
compile with ZERO DATA collectives. A collective that lands inside a loop
runs once per solver iteration instead of once per update; the payload
bounds above would not catch a small-but-per-iteration regression.
``assert_entity_solves_collective_free`` walks the compiled module's
``while`` bodies/conditions (transitively through called computations) and
fails on any collective there EXCEPT single-element all-reduces: a globally
batched ``while_loop`` over sharded lanes must agree on termination, so its
condition carries one scalar ``pred[]`` convergence-consensus all-reduce per
iteration check — semantically unavoidable (the per-bucket mesh path's
jitted solves have the identical op), latency-bound not bandwidth-bound,
and already named legal by the profile above ("convergence predicates").
"""

from __future__ import annotations

import dataclasses
import re

_COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# `%name = <shape-or-tuple> <kind>(`  — shape may be a tuple like
# `(f32[], f32[24]{0})`; layout suffixes `{1,0}` are part of the token, and a
# TPU module's layouts carry tiling in parentheses (`f32[64]{0:T(128)}`), so
# the tuple form admits one level of nested parentheses.
_OP_RE = re.compile(
    r"=\s*(\((?:[^()]|\([^()]*\))*\)|\S+)\s+("
    + "|".join(_COLLECTIVE_KINDS)
    + r")(-start)?\("
)
_SHAPE_RE = re.compile(r"[a-z0-9]+\[([0-9,]*)\]")


@dataclasses.dataclass(frozen=True)
class Collective:
    kind: str
    shape: str  # raw result-shape text
    elements: int  # total elements across the (possibly tuple) result

    @staticmethod
    def parse_all(compiled_text: str) -> list:
        out = []
        for line in compiled_text.splitlines():
            m = _OP_RE.search(line)
            if not m:
                continue
            shape_text, kind, is_start = m.group(1), m.group(2), bool(m.group(3))
            shapes = _SHAPE_RE.findall(shape_text)
            if is_start and len(shapes) > 1:
                # async form: the result tuple carries (operand, result) —
                # counting both would double the payload and fail a legal
                # full-size gather; only the RESULT half rides the wire
                shapes = shapes[-1:]
            elements = 0
            for dims in shapes:
                count = 1
                for d in dims.split(","):
                    if d:
                        count *= int(d)
                elements += count
            out.append(Collective(kind=kind, shape=shape_text, elements=elements))
        return out


def assert_collective_profile(
    compiled_text: str,
    *,
    grad_elements: int,
    table_elements: int,
    n_samples: int,
    max_collectives: int = 48,
    bucket_block_elements: int = 0,
) -> list:
    """Fail if the compiled module's collectives exceed the healthy GLMix
    profile. Returns the parsed collectives for reporting.

    grad_elements: fixed-effect gradient size D.
    table_elements: largest padded per-entity coefficient table (E_pad * K).
    Legal all-reduce: value+gradient tuple and/or a coefficient-table
    scatter-combine (XLA may fuse them into one tuple-shaped op). Legal
    all-gather: entity tables and [n_samples] score vectors.

    bucket_block_elements (the sharded RE coordinate-update program only):
    largest per-bucket [E_pad, S] block. GSPMD lowers the once-per-update
    offset gather (sample-sharded [N] source, entity-sharded [E, S] indices)
    as a masked local gather plus an all-reduce of the [E, S] result — an
    extra legal all-reduce class, bounded by the bucket's sample-id block
    and sitting OUTSIDE the solver loops (``loop_collectives`` proves that
    separately). 0 (the default) disables the class — the fused whole-pass
    profile has no such op.
    """
    collectives = Collective.parse_all(compiled_text)
    biggest_gather = max(table_elements, n_samples)
    biggest_reduce = max(
        grad_elements + 1 + table_elements, bucket_block_elements
    )
    for c in collectives:
        if c.kind == "all-reduce":
            assert c.elements <= biggest_reduce, (
                f"all-reduce payload {c.shape} ({c.elements} elements) exceeds "
                f"the gradient+entity-table bound {biggest_reduce} — a data- "
                f"or bucket-block-sized reduction rides the wire every solver "
                f"iteration"
            )
        elif c.kind == "all-gather":
            assert c.elements <= biggest_gather, (
                f"all-gather result {c.shape} ({c.elements} elements) exceeds "
                f"the entity-table/score bound {biggest_gather} — the mesh is "
                f"gathering dataset-sized tensors"
            )
        else:
            raise AssertionError(
                f"unexpected {c.kind} in the compiled pass ({c.shape}): the "
                f"healthy profile has none; if this is a deliberate sharding "
                f"change, extend assert_collective_profile"
            )
    assert len(collectives) <= max_collectives, (
        f"{len(collectives)} collectives in one pass (cap {max_collectives}): "
        f"collective count must scale with solver program count, not entities"
    )
    return collectives


# --------------------------------------------------------------------------
# loop-body collective scan: the RE-bucket-solves-are-comm-free guard
# --------------------------------------------------------------------------

# `%name (params...) -> result {` or `ENTRY %name ... {` — one per
# computation. The parameter list is matched GREEDILY (`\(.*\)`): real XLA
# while bodies take a single TUPLE-typed parameter whose type nests parens
# (`(arg_tuple.5: (s32[], f32[8])) -> ...`), which a lazy `[^)]*` would stop
# at — silently dropping every loop body from the scan and making the
# collective-free assertion vacuous. The header is one line, so greedy is
# safe.
_COMPUTATION_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
# computation references an op can carry: loop bodies/conditions, fusions,
# reducers, conditional branch LISTS (`branch_computations={%a, %b}` — every
# member must be followed, not just the first)
_CALLED_RE = re.compile(
    r"(?:body|condition|to_apply|calls|branch_computations)="
    r"(\{[^}]*\}|%?[\w\.\-]+)"
)
_NAME_RE = re.compile(r"[\w\.\-]+")
_WHILE_RE = re.compile(
    r"while\(.*?\)(?:,\s*(?:condition|body)=%?([\w\.\-]+))(?:,\s*(?:condition|body)=%?([\w\.\-]+))?"
)
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVE_KINDS) + r")(?:-start)?\("
)


def _computations(compiled_text: str) -> dict:
    """Split compiled HLO text into {computation name: [body lines]}."""
    comps: dict = {}
    current = None
    for line in compiled_text.splitlines():
        m = _COMPUTATION_RE.match(line)
        if m:
            current = m.group(1)
            comps[current] = []
        elif current is not None:
            comps[current].append(line)
    return comps


def loop_collectives(compiled_text: str) -> list:
    """Collectives reachable from any ``while`` op's body or condition
    (transitively through ``to_apply``/``calls``/nested loops). Each entry is
    ``(computation name, HLO line, result elements)``. A healthy batched
    solve shows only single-element convergence-predicate all-reduces here
    (see ``assert_entity_solves_collective_free``); data-sized entries mean
    per-iteration communication."""
    comps = _computations(compiled_text)
    seeds: set = set()
    for lines in comps.values():
        for line in lines:
            m = _WHILE_RE.search(line)
            if m:
                seeds.update(g for g in m.groups() if g)
    # transitive closure over computations called from loop bodies
    reached = set(seeds)
    frontier = list(seeds)
    while frontier:
        name = frontier.pop()
        for line in comps.get(name, ()):
            for group in _CALLED_RE.findall(line):
                for ref in _NAME_RE.findall(group):
                    if ref in comps and ref not in reached:
                        reached.add(ref)
                        frontier.append(ref)
    out = []
    for name in sorted(reached):
        for line in comps.get(name, ()):
            if _COLLECTIVE_LINE_RE.search(line):
                parsed = Collective.parse_all(line)
                elements = parsed[0].elements if parsed else -1
                out.append((name, line.strip(), elements))
    return out


# ops a constant can hide behind without changing its literal-ness
_CONST_PASSTHROUGH = ("bitcast(", "broadcast(", "reshape(", "copy(")
_AG_OPERAND_RE = re.compile(r"all-gather(?:-start)?\(\S+\s+%([\w\.\-]+)\)")
_DEF_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=")
_OPERAND_REF_RE = re.compile(r"%([\w\.\-]+)")


def _is_constant_gather(line: str, defs: dict) -> bool:
    """True when an all-gather's operand chains back (through bitcast/
    broadcast/reshape/copy) to a compile-time ``constant``: GSPMD sometimes
    materializes a replicated literal by sharding the constant and gathering
    it back. Every device already holds the literal — nothing lane-private
    crosses the wire — so the settings-axis guard tolerates exactly this
    (outside loops; the loop scan separately rejects ANY in-loop gather)."""
    m = _AG_OPERAND_RE.search(line)
    if not m:
        return False
    name = m.group(1)
    for _ in range(4):  # bounded chain walk
        d = defs.get(name)
        if d is None:
            return False
        if "constant(" in d:
            return True
        rhs = d.split("=", 1)[1]
        if not any(op in rhs for op in _CONST_PASSTHROUGH):
            return False
        refs = _OPERAND_REF_RE.findall(rhs)
        if not refs:
            return False
        name = refs[0]
    return False


def assert_settings_axis_collective_free(compiled_text: str) -> int:
    """The mesh x population contract (the fused sweep program of
    ``parallel/game.population_sweep_fn`` with the SETTINGS axis sharded over
    the mesh): lanes are independent by construction — a lane's offsets come
    only from its own coordinates' scores, the shared datasets replicate,
    and no cross-lane reduction exists anywhere in the trace — so the
    compiled module must carry ZERO data collectives ANYWHERE, not merely
    outside solver loops. Stricter than ``assert_collective_profile`` (which
    budgets the entity-sharded pass's legal gather/scatter exchange): here
    there is nothing to exchange at all. Two op classes are tolerated:

    - the single-element all-reduce — the batched ``while_loop``'s
      termination consensus over lane shards (and the freeze flags' scalar
      combines), latency-bound and payload-free;
    - an all-gather whose operand is a COMPILE-TIME CONSTANT
      (``_is_constant_gather``): GSPMD occasionally lowers a replicated
      zero literal (the early-exit masking's ``where(active, f, 0)``) as
      shard-the-constant-then-gather. The literal is identical on every
      device, so no lane data moves — and the in-loop scan below proves
      none of these (or anything else) runs per solver iteration.

    Any collective of any kind INSIDE a solver while-loop body/condition
    other than the scalar predicate consensus is fatal regardless of
    operand. Returns the count of tolerated ops for reporting."""
    defs: dict = {}
    for line in compiled_text.splitlines():
        m = _DEF_NAME_RE.match(line)
        if m:
            defs[m.group(1)] = line
    collectives = []
    tolerated = 0
    for line in compiled_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        parsed = Collective.parse_all(line)[0]
        if parsed.kind == "all-reduce" and parsed.elements == 1:
            tolerated += 1
            continue
        if parsed.kind == "all-gather" and _is_constant_gather(line, defs):
            tolerated += 1
            continue
        collectives.append(parsed)
    assert not collectives, (
        f"{len(collectives)} data collective(s) in the population sweep "
        f"module — the settings axis is no longer embarrassingly parallel "
        f"(a cross-lane op or a resharding snuck into the fused program): "
        + "; ".join(f"{c.kind} {c.shape}" for c in collectives[:4])
    )
    in_loop = [
        (name, line, elements)
        for name, line, elements in loop_collectives(compiled_text)
        if elements != 1 or "all-reduce" not in line
    ]
    assert not in_loop, (
        f"{len(in_loop)} collective(s) inside the population solver loops "
        f"(they run per solver ITERATION): "
        + "; ".join(f"{n}: {l[:80]}" for n, l, _ in in_loop[:4])
    )
    return tolerated


def assert_feature_axis_profile(
    compiled_text: str,
    *,
    grad_elements: int,
    n_samples: int,
    max_loop_data_collectives: int = 12,
    max_collectives: int = 64,
) -> dict:
    """The 2-D (data x model) fixed-effect update program's collective
    contract — feature-partitioned distributed CD (1411.6520): each model
    shard owns a coefficient block, and the ONE thing devices must exchange
    per solver iteration is margin partial sums (an all-reduce of at most
    [n_samples]) plus the gradient-block exchange (at most [grad_elements]).
    Audits ``FixedEffectCoordinate.compiled_update_hlo`` — exactly the
    program training dispatches.

    What the compiled module may carry (calibrated against the real lowered
    program on an emulated 8-device 4x2 mesh, dense AND sparse storage):

    - **all-reduce**: margin partials (GSPMD emits them shard-local,
      [n_samples / n_data], for dense block layouts and global [n_samples]
      for the sparse flat-nnz layout), gradient blocks (<= [grad_elements]),
      and the scalar convergence predicates of batched while-loops;
    - **all-gather**: the sparse layout's coefficient rebuild for
      ``take(w, cols)`` (<= [grad_elements]) and margin re-distribution
      (<= [n_samples]). Dense lowers with no gathers at all;
    - **nothing else**: no reduce-scatter / all-to-all / collective-permute,
      and no payload above ``max(grad_elements, n_samples)`` anywhere — a
      larger payload means the design matrix (or its nnz arrays) is riding
      the wire, i.e. the mesh is densifying or resharding the data instead
      of exchanging margins.

    Inside solver while-loops, payload-bearing collectives run once per
    ITERATION, so they are additionally gated by COUNT
    (``max_loop_data_collectives``; the calibration lowering shows 4 for
    dense, 8 for sparse): a count blow-up is how an accidentally unrolled
    or per-column loop manifests while each individual payload still looks
    legal. Single-element all-reduce predicates are free — they are the
    loop-termination consensus every sharded ``while_loop`` carries.

    ``grad_elements``/``n_samples`` are the PADDED global counts (the model-
    and data-axis multiples placement padded to). Returns a profile dict
    ``{total, loop_data, loop_predicates}`` for reporting."""
    collectives = Collective.parse_all(compiled_text)
    bound = max(grad_elements, n_samples)
    for c in collectives:
        if c.kind not in ("all-reduce", "all-gather"):
            raise AssertionError(
                f"unexpected {c.kind} in the 2-D fixed-effect update "
                f"({c.shape}): the feature-axis profile is all-reduce/"
                f"all-gather only (1411.6520's margin-exchange pattern); a "
                f"{c.kind} means the partitioner is resharding data mid-solve"
            )
        assert c.elements <= bound, (
            f"{c.kind} payload {c.shape} ({c.elements} elements) exceeds the "
            f"margin/gradient bound max({grad_elements}, {n_samples}) = "
            f"{bound} — a matrix- or nnz-sized tensor rides the wire instead "
            f"of margin partial sums"
        )
    assert len(collectives) <= max_collectives, (
        f"{len(collectives)} collectives in the 2-D fixed-effect update "
        f"(cap {max_collectives}): count must stay O(solver program "
        f"structure), not O(features)"
    )
    loop = loop_collectives(compiled_text)
    predicates = [e for e in loop if e[2] == 1 and "all-reduce" in e[1]]
    data = [e for e in loop if not (e[2] == 1 and "all-reduce" in e[1])]
    for name, line, elements in data:
        assert 0 < elements <= bound, (
            f"in-loop collective in {name} with payload {elements} exceeds "
            f"the margin/gradient bound {bound} (runs per solver iteration): "
            f"{line[:100]}"
        )
    assert len(data) <= max_loop_data_collectives, (
        f"{len(data)} payload-bearing collectives inside solver while-loops "
        f"(cap {max_loop_data_collectives}) — each runs per solver "
        f"ITERATION; a count blow-up here is an unrolled or per-column "
        f"communication pattern even when every payload looks legal"
    )
    return {
        "total": len(collectives),
        "loop_data": len(data),
        "loop_predicates": len(predicates),
    }


def assert_entity_solves_collective_free(compiled_text: str) -> int:
    """Fail if any DATA collective appears inside a ``while`` body/condition
    of the compiled module. For the random-effect coordinate update this is
    the embarrassingly-parallel contract: entity-sharded bucket solves need
    no data communication — every payload-bearing collective (offset/table
    gathers, the table scatter-combine, the finiteness all-reduce) sits
    OUTSIDE the solver loops and runs once per update, not once per solver
    iteration. The ONE legal in-loop collective is the single-element
    all-reduce of the loop's convergence predicate (global termination
    consensus over sharded lanes — present in every batched sharded
    ``while_loop``, including the per-bucket path's). Returns the count of
    those tolerated predicate all-reduces for reporting."""
    found = loop_collectives(compiled_text)
    data = [
        (name, line, elements)
        for name, line, elements in found
        if elements != 1 or "all-reduce" not in line
    ]
    assert not data, (
        f"{len(data)} data collective(s) inside solver while-loops — the "
        f"entity-sharded bucket solves are no longer communication-free "
        f"(each runs per solver ITERATION): "
        + "; ".join(f"{name}: {line[:100]}" for name, line, _ in data[:4])
    )
    return len(found)
