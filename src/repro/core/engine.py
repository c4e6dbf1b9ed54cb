"""The Datalog runtime: stratum-ordered evaluation of planned programs.

``Engine`` is the user-facing entry point (see ``examples/quickstart.py``):

    eng = Engine(program_text, db={"arc": edges}, caps={"tc": 1 << 20})
    eng.run()
    tc = eng.query("tc")          # numpy rows (full perfect model)
    dist = eng.query_agg("spath") # (rows, values)

    # demand-driven evaluation (magic-sets rewrite, evaluates only what the
    # query needs):
    rows = eng.ask("tc", (1, None))          # == full tc restricted to src 1
    eng2 = Engine(text + "?- tc(1, X).", db=...).run()  # same, via ?- goal

Evaluation follows the iterated-fixpoint (perfect-model) schedule from §2:
SCCs of the PCG evaluate leaves-first; recursive SCCs run the PSN fixpoint of
Algorithm 1 under ``jax.lax.while_loop``; results materialize and become base
relations for higher strata.  Aggregates-in-recursion run PreM-transferred
(eager ⊕-merge per iteration) — the planner refuses programs where PreM fails
structurally.

Each SCC executes through a :class:`GroupExecutor`, a pure function of its
*data*: EDB rows, join indexes and seed-fact keys all enter the jitted
fixpoint as arguments, so the compiled runner depends only on the plan
structure (rule pipelines, capacities, bit widths).  Runners are cached
globally on that structural key — two engines whose plans differ only in
data (e.g. repeated ``ask()`` calls whose magic rewrites differ only in the
seed constants) share one trace/compile.  ``fixpoint_trace_count()`` exposes
the trace counter so tests (and the serving layer) can assert the Nth query
with the same padded shapes skips compilation.

Query-driven runs plan through the magic-sets pass (``magic.py``): the
program is adorned from the query goal, guarded by magic predicates seeded
with the query constants, and only the demanded strata evaluate.  When a
query binds the pivot of a decomposable binary recursion, :meth:`Engine.
ask_dense` additionally lowers to the dense ``form="vector"`` fixpoint seeded
with the query frontier row.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Union

import jax
import jax.numpy as jnp
import numpy as np

from .ir import Const, Literal, Program, Term, Var, fresh_var
from .magic import (MagicError, detect_frontier_lowering,
                    frontier_query_source)
from .parser import parse_program, parse_query
from .planner import (CompiledRule, EdbJoinStep, GroupPlan, PlanError,
                      PlanOptions, ProgramPlan, SourceDelta, SourceEdb,
                      batch_adornment, plan_program)
from .relation import EMPTY, AggTable, FactTable, Schema, _MERGE_INIT
from . import seminaive as _sn
from . import sparse as _sparse
from .seminaive import (Bindings, EdbIndex, build_edb_index, join_edb,
                        join_idb_prefix, pack_warm_rows, quantize_rows,
                        reachable_from_dense, single_source_distances_dense)


class CapacityError(RuntimeError):
    pass


QuerySpec = Union[str, Literal, tuple]


def as_query_literal(query: QuerySpec, constants: dict[str, int] | None = None) -> Literal:
    """Normalize the query forms ``"tc(1, X)"`` / ``("tc", (1, None))`` /
    :class:`Literal` into a query goal literal (None/vars = free)."""
    if isinstance(query, Literal):
        return query
    if isinstance(query, str):
        return parse_query(query, constants)
    if isinstance(query, tuple) and len(query) == 2 and isinstance(query[0], str):
        pred, args = query
        terms: list[Term] = []
        for a in args:
            if a is None:
                terms.append(fresh_var("_q"))
            elif isinstance(a, (Var, Const)):
                terms.append(a)
            else:
                terms.append(Const(int(a)))
        return Literal(pred, tuple(terms))
    raise ValueError(f"cannot interpret query spec {query!r}")


@dataclasses.dataclass
class GroupStats:
    iterations: int
    generated: int  # facts produced before dedup (paper Tables 7/8)


def repeated_var_groups(q: Literal) -> list[list[int]]:
    """Argument positions sharing a variable (``tc(X, X)`` -> [[0, 1]]).

    Queries may repeat variables; the magic rewrite adorns them as free, so
    the evaluated model is unconstrained and the equality must filter the
    result (like constants do)."""
    groups: dict[str, list[int]] = {}
    for i, a in enumerate(q.args):
        if isinstance(a, Var):
            groups.setdefault(a.name, []).append(i)
    return [ps for ps in groups.values() if len(ps) > 1]


def query_row_mask(q: Literal, rows, vals, info=None) -> np.ndarray:
    """Row mask restricting an evaluated model to a query goal: constants
    match their column, repeated variables must be pairwise equal.

    The ONE filtering semantics shared by ``Engine.ask`` (EDB selections),
    ``Engine._finalize_query``, ``Engine._verify_ask`` and the serving
    layer's templates.  ``info`` (a planner ``PredInfo``) maps aggregate
    literal positions onto key columns / the values array; ``info=None``
    treats every position as a direct row column (EDB relations).
    """
    def col(pos):
        if info is not None and info.is_agg and pos == info.agg_pos:
            return np.asarray(vals)
        return np.asarray(rows[:, pos if info is None else info.key_rank(pos)])

    mask = np.ones(len(rows), bool)
    for i, a in enumerate(q.args):
        if isinstance(a, Const):
            mask &= col(i) == a.value
    for ps in repeated_var_groups(q):
        for pos in ps[1:]:
            mask &= col(ps[0]) == col(pos)
    return mask


def split_qid_answers(pred: str, rows, vals, info, qlits, qids=None) -> list:
    """Per-seed attribution: split a qid-tagged model into per-query answers.

    ``rows``/``vals`` carry the query-id in key column 0; for each goal the
    qid selects its slice, then the goal's own constants / repeated variables
    filter exactly like the single-query path (the demanded set may exceed
    the queried set).  The ONE splitting semantics shared by
    ``Engine._finalize_batch`` and the serving layer's batched templates.
    ``qids`` overrides the per-goal qid tags (default: position order).
    """
    out = []
    for k, q in enumerate(qlits):
        qid = k if qids is None else qids[k]
        shifted = Literal(pred, (Const(qid),) + q.args)
        mask = query_row_mask(shifted, rows, vals, info)
        r = rows[mask][:, 1:]  # drop the qid column
        out.append((r, vals[mask]) if info.is_agg else r)
    return out


# ---------------------------------------------------------------------------
# Cached group runners
# ---------------------------------------------------------------------------

#: structural plan key -> jitted group runner (shared across Engine instances)
_RUNNER_CACHE: dict[tuple, Callable] = {}
_RUNNER_CACHE_LIMIT = 256


def fixpoint_trace_count() -> int:
    """Number of times a fixpoint has been (re-)traced process-wide — group
    runners, cached dense fixpoints and CSR fixpoints alike (the counter
    lives in ``seminaive`` so every engine representation shares it)."""
    return _sn.trace_count()


def clear_runner_cache() -> None:
    _RUNNER_CACHE.clear()


class GroupExecutor:
    """One GroupPlan as a pure function of its data.

    Every value input — EDB rows, join indexes, seed-fact keys — enters the
    jitted fixpoint as an argument; the trace depends only on the plan
    *structure* (compiled rule pipelines, table capacities, bit widths,
    iteration cap).  Runners cache globally on that structural key, so the
    Nth structurally identical evaluation with the same array shapes reuses
    the compiled fixpoint instead of re-tracing.
    """

    def __init__(self, gp: GroupPlan, caps: dict[str, int], bits: int,
                 jcap: int, max_iters: int):
        self.gp = gp
        self.caps = caps  # fully resolved per predicate (aliases applied)
        self.bits = bits
        self.jcap = jcap
        self.max_iters = max_iters

    def structural_key(self) -> tuple:
        gp = self.gp
        return (
            tuple(sorted((p, repr(i)) for p, i in gp.preds.items())),
            tuple(repr(cr) for cr in gp.exit_rules),
            tuple(repr(cr) for cr in gp.rec_rules),
            gp.recursive,
            tuple(sorted(self.caps.items())),
            self.bits, self.jcap, self.max_iters,
        )

    def runner(self) -> Callable:
        key = self.structural_key()
        run = _RUNNER_CACHE.get(key)
        if run is None:
            run = jax.jit(self._run_group)
            if len(_RUNNER_CACHE) >= _RUNNER_CACHE_LIMIT:
                _RUNNER_CACHE.pop(next(iter(_RUNNER_CACHE)))
            _RUNNER_CACHE[key] = run
        return run

    # -- plumbing -----------------------------------------------------------

    def _schema(self, info) -> Schema:
        return Schema(tuple([self.bits] * info.key_arity))

    def _empty_table(self, info):
        if info.is_agg:
            kind = {"min": "min", "max": "max", "count": "count", "mcount": "count",
                    "sum": "sum", "msum": "sum"}[info.agg]
            return AggTable.empty(self.caps[info.name], kind)
        return FactTable.empty(self.caps[info.name])

    # -- group evaluation ---------------------------------------------------

    def _run_group(self, facts, edb):
        """facts: {pred: (packed_keys, values|None)}; edb: {'idx': {...},
        'src': {...}} — all jit arguments.  Returns (state, iters, gen)."""
        _sn.bump_trace_count()  # executes at trace time only
        gp = self.gp
        state = {p: {"all": self._empty_table(info), "delta": self._empty_table(info)}
                 for p, info in gp.preds.items()}

        # facts (rules with empty bodies; includes magic seed facts)
        for pred in sorted(facts):
            info = gp.preds[pred]
            keys, vals = facts[pred]
            contrib = (keys, vals, jnp.zeros((), bool))
            state[pred]["all"], _ = self._merge_contribs(
                state[pred]["all"], [contrib], info)

        # exit rules
        gen = jnp.int64(0)
        contribs = {p: [] for p in gp.preds}
        for cr in gp.exit_rules:
            k, v, n, ovf = self._run_pipeline(cr, state, edb)
            contribs[cr.head_pred].append((k, v, ovf))
            gen = gen + n
        for pred, info in gp.preds.items():
            allt, _ = self._merge_contribs(state[pred]["all"], contribs[pred], info)
            state[pred]["all"] = allt
            state[pred]["delta"] = allt  # first delta = everything so far

        iters = jnp.int32(0)
        if gp.recursive and gp.rec_rules:
            state, iters, gen = self._psn_loop(state, edb, gen)
        return state, iters, gen

    def _psn_loop(self, state, edb, gen0):
        """Algorithm 1: do { delta = T(delta) − all; all ∪= delta } while delta."""
        preds = sorted(self.gp.preds)

        def cond(carry):
            st, it, gen = carry
            alive = jnp.zeros((), bool)
            for p in preds:
                alive = alive | (st[p]["delta"].count > 0)
            return alive & (it < self.max_iters)

        def body(carry):
            st, it, gen = carry
            contribs = {p: [] for p in preds}
            for cr in self.gp.rec_rules:
                k, v, n, ovf = self._run_pipeline(cr, st, edb)
                contribs[cr.head_pred].append((k, v, ovf))
                gen = gen + n
            new_st = {}
            for p in preds:
                info = self.gp.preds[p]
                allt, delta = self._merge_contribs(st[p]["all"], contribs[p], info)
                new_st[p] = {"all": allt, "delta": delta}
            return new_st, it + 1, gen

        return jax.lax.while_loop(cond, body, (state, jnp.int32(0), gen0))

    def _merge_contribs(self, allt, contribs, info):
        """Concat *all* rule contributions for a predicate, merge once.

        A single merge is required for additive aggregates (count/sum): the
        delta must carry the final post-iteration value per key, not a stack
        of intermediate snapshots.
        """
        if not contribs:
            empty = self._empty_table(info)
            return allt, empty
        ovf = allt.overflow
        for _, _, o in contribs:
            ovf = ovf | o
        keys = jnp.concatenate([k for k, _, _ in contribs])
        if info.is_agg:
            vals = jnp.concatenate([v for _, v, _ in contribs])
            merged, delta = allt.merge(keys, vals)
        else:
            new = FactTable.from_keys(keys, allt.capacity)
            delta = new.difference(allt)
            merged = allt.union(delta)
        merged = dataclasses.replace(merged, overflow=merged.overflow | ovf)
        return merged, delta

    def _join_idb(self, b: Bindings, step, state) -> Bindings:
        """Join bindings against an IDB table (the recursive relation).

        Prefix joins ride the table's own sort order (the decomposable read of
        the paper's Fig. 4 plan).  Non-prefix joins re-pack the table with the
        probe columns leading and re-sort — the in-engine equivalent of a
        repartition/shuffle, and exactly what the RWA cost model charges for.
        """
        info = self.gp.preds[step.pred]
        t = state[step.pred]["all"]
        schema = self._schema(info)
        values = getattr(t, "values", None)
        n = len(step.probe_cols)
        if step.is_prefix:
            return join_idb_prefix(b, t.keys, t.count, step.probe_vars, schema,
                                   n, values, dict(step.intro), self.jcap)
        # --- shuffle path: permute columns so probe cols lead, re-sort
        perm = list(step.probe_cols) + [c for c in range(info.key_arity)
                                        if c not in step.probe_cols]
        unpacked = schema.unpack(t.keys)
        perm_schema = Schema(tuple(schema.bits[c] for c in perm))
        valid_rows = jnp.arange(t.capacity) < t.count
        repacked = perm_schema.pack([unpacked[c] for c in perm])
        repacked = jnp.where(valid_rows, repacked, EMPTY)
        order = jnp.argsort(repacked)
        sorted_keys = repacked[order]
        sorted_values = values[order] if values is not None else None
        remapped_intro = {
            v: ("value" if c == "value" else perm.index(c))
            for v, c in dict(step.intro).items()
        }
        return join_idb_prefix(b, sorted_keys, t.count, step.probe_vars, perm_schema,
                               n, sorted_values, remapped_intro, self.jcap)

    # -- pipeline execution -------------------------------------------------

    def _run_pipeline(self, cr: CompiledRule, state, edb):
        """Execute one compiled rule; return (head_keys, head_values, produced)."""
        gp = self.gp

        # --- source bindings
        if isinstance(cr.source, SourceDelta):
            info = gp.preds[cr.source.pred]
            t = state[cr.source.pred]["delta"]
            schema = self._schema(info)
            unpacked = schema.unpack(t.keys)
            cols = {}
            for v, c in zip(cr.source.key_vars, unpacked):
                if v:
                    cols[v] = c
            if cr.source.value_var:
                cols[cr.source.value_var] = t.incs if cr.use_increment else t.values
            valid = jnp.arange(t.capacity) < t.count
            b = Bindings(cols, valid, t.overflow & False)
        else:
            rows, valid = edb["src"][(cr.source.rel, cr.source.select)]
            cols = {v: rows[:, i].astype(jnp.int32) for v, i in cr.source.intro}
            b = Bindings(cols, valid, jnp.zeros((), bool))

        # --- joins
        for step in cr.joins:
            if isinstance(step, EdbJoinStep):
                idx = edb["idx"][(step.rel, step.build_cols)]
                if step.negated:
                    key_schema = Schema(tuple([self.bits] * len(step.probe_vars)))
                    shape = b.valid.shape
                    pcols = [b.cols[v] if isinstance(v, str)
                             else jnp.full(shape, v, jnp.int32)
                             for v in step.probe_vars]
                    probe = key_schema.pack(pcols)
                    probe = jnp.where(b.valid, probe, EMPTY)
                    pos = jnp.clip(jnp.searchsorted(idx.keys, probe), 0, idx.keys.shape[0] - 1)
                    hit = (idx.keys[pos] == probe) & (pos < idx.count)
                    b = Bindings(b.cols, b.valid & ~hit, b.overflow)
                else:
                    b = join_edb(b, idx, step.probe_vars, step.build_cols,
                                 dict(step.intro), self.bits, self.jcap)
            else:
                b = self._join_idb(b, step, state)

        # --- interpreted goals
        def term_col(t, ref_shape):
            if isinstance(t, Var):
                return b.cols[t.name]
            return jnp.full(ref_shape, t.value, jnp.int32)

        shape = b.valid.shape
        valid = b.valid
        for a in cr.ariths:
            l, r = term_col(a.lhs, shape), term_col(a.rhs, shape)
            res = (l + r if a.op == "+" else
                   l * r if a.op == "*" else l - r)
            if a.target.name in b.cols:  # already bound => equality constraint
                valid = valid & (b.cols[a.target.name] == res)
            else:
                b.cols[a.target.name] = res
        for c in cr.comps:
            # '=' with one side unbound acts as a binding (L = L1 aliases)
            if c.op == "=":
                if isinstance(c.lhs, Var) and c.lhs.name not in b.cols:
                    b.cols[c.lhs.name] = term_col(c.rhs, shape)
                    continue
                if isinstance(c.rhs, Var) and c.rhs.name not in b.cols:
                    b.cols[c.rhs.name] = term_col(c.lhs, shape)
                    continue
            l, r = term_col(c.lhs, shape), term_col(c.rhs, shape)
            op = {"<": l < r, "<=": l <= r, ">": l > r, ">=": l >= r,
                  "=": l == r, "!=": l != r}[c.op]
            valid = valid & op

        # --- head projection
        info = gp.preds[cr.head_pred]
        schema = self._schema(info)
        key_cols = []
        for hk in cr.head_keys:
            key_cols.append(b.cols[hk] if isinstance(hk, str) else jnp.full(shape, hk, jnp.int32))
        keys = schema.pack(key_cols) if key_cols else jnp.zeros(shape, jnp.int64)
        keys = jnp.where(valid, keys, EMPTY)
        if info.is_agg:
            if isinstance(cr.head_value, str):
                vals = b.cols[cr.head_value].astype(jnp.int32)
            else:
                vals = jnp.full(shape, cr.head_value, jnp.int32)
            init = _MERGE_INIT["min" if info.agg == "min" else
                               "max" if info.agg == "max" else "sum"]
            vals = jnp.where(valid, vals, init)
        else:
            vals = None
        produced = jnp.sum(valid).astype(jnp.int64)
        return keys, vals, produced, b.overflow


class Engine:
    def __init__(
        self,
        program: Union[str, Program],
        db: dict[str, np.ndarray],
        bits: int = 18,
        caps: dict[str, int] | None = None,
        default_cap: int = 1 << 16,
        join_cap: int | None = None,
        max_iters: int = 1 << 16,
        constants: dict[str, int] | None = None,
        query: QuerySpec | None = None,
        batch: list | tuple | None = None,
        magic: bool = True,
        sparse: bool | None = None,
        sparse_threshold: float | None = None,
        bucket_floors: dict[str, int] | None = None,
        tune=None,
    ):
        if isinstance(program, str):
            program = parse_program(program, constants=constants)
        self.source_program = program
        if query is None and batch is None and program.queries:
            if len(program.queries) > 1:
                # multi-goal program: same-shape goals evaluate as ONE
                # qid-batched fixpoint (run() + batch_results())
                shapes = {(q.pred, batch_adornment(program, q))
                          for q in program.queries}
                if len(shapes) > 1 or not magic:
                    raise ValueError(
                        f"program has {len(program.queries)} '?-' goals of "
                        f"{len(shapes)} shapes (magic={magic}); one engine "
                        "plans one magic-batched shape — use ask_batch() "
                        "for mixed goals or demand-only evaluation")
                batch = tuple(program.queries)
            else:
                query = program.queries[0]
        if query is not None and batch is not None:
            raise ValueError("pass query= or batch=, not both")
        qlit = as_query_literal(query, constants) if query is not None else None
        blits = (tuple(as_query_literal(b, constants) for b in batch)
                 if batch is not None else None)
        self.magic = magic
        self.plan: ProgramPlan = plan_program(
            program, PlanOptions(
                query=qlit, batch=blits, magic=magic, sparse=sparse,
                sparse_threshold=sparse_threshold,
                bucket_floors=tuple(sorted((bucket_floors or {}).items())),
                tune=tune))
        # groups/facts reference the post-pass (possibly magic-rewritten) rules
        self.program = self.plan.rewritten
        self.bits = bits
        self.caps = dict(caps or {})
        self.default_cap = default_cap
        self.join_cap = join_cap
        self.max_iters = max_iters
        def _norm(v):
            v = np.asarray(v, np.int64)
            v = v[:, None] if v.ndim == 1 else v  # reshape(-1) chokes on 0 rows
            # EDB relations are SETS of facts: an exact duplicate row is the
            # same fact, and keeping it would double-count the duplicated
            # body binding in additive (count/sum) aggregates — bool/min/max
            # are duplicate-insensitive, which is why this went unnoticed
            return np.unique(v, axis=0) if len(v) else v
        self.db: dict[str, np.ndarray] = {k: _norm(v) for k, v in db.items()}
        limit = (1 << bits) - 1
        for k, v in self.db.items():
            if v.size and (v.min() < 0 or v.max() > limit):
                raise ValueError(f"relation {k} exceeds {bits}-bit domain")
        self.materialized: dict[str, tuple[np.ndarray, np.ndarray | None]] = {}
        self.stats: dict[str, GroupStats] = {}
        self._index_cache: dict[tuple[str, tuple[int, ...]], EdbIndex] = {}
        self._pred_info = {p: info for gp in self.plan.groups
                           for p, info in gp.preds.items()}
        self._warm: dict[str, tuple[np.ndarray, np.ndarray | None]] = {}
        self._batch_out: list | None = None

    # -- public API ----------------------------------------------------------

    def run(self, warm: dict[str, tuple] | None = None) -> "Engine":
        """Evaluate all strata.  ``warm`` maps predicate -> previously
        materialized (rows, values): monotone tables re-enter the fixpoint
        from that lower bound (appends resume from the delta frontier instead
        of recomputing — see ``seminaive.pack_warm_rows``).  Warm-starting is
        only sound for programs monotone under appends (no negation, no
        additive aggregates) — anything else raises rather than silently
        double-billing warm counts or keeping refuted facts."""
        if warm and not self.program.monotone_under_appends():
            raise PlanError(
                "run(warm=) on a program with negation or count/sum "
                "aggregates is unsound (warm rows would re-merge into "
                "additive totals / keep non-monotone facts); re-run cold")
        self._warm = dict(warm or {})
        try:
            for gp in self.plan.groups:
                self._eval_group(gp)
        finally:
            self._warm = {}
        if self.plan.options.batch is not None:
            self._finalize_batch()
        elif self.plan.query_pred is not None:
            self._finalize_query()
        return self

    def query(self, pred: str) -> np.ndarray:
        rows, _ = self._result(pred)
        return rows

    def query_agg(self, pred: str) -> tuple[np.ndarray, np.ndarray]:
        rows, vals = self._result(pred)
        assert vals is not None, f"{pred} is not an aggregate predicate"
        return rows, vals

    def ask(self, pred: QuerySpec, args: tuple | None = None, verify: bool = False,
            caps: dict[str, int] | None = None, default_cap: int | None = None,
            join_cap: int | None = None):
        """Demand-driven query: magic-rewrite, evaluate only demanded strata.

        ``ask("tc", (1, None))`` returns exactly the rows of the full-model
        ``query("tc")`` with first column 1 — computed bottom-up on the
        magic-restricted program, not by post-filtering the perfect model.
        Aggregate predicates return ``(rows, values)``.  ``verify=True``
        cross-checks the result against the full-model path (slow; testing).

        ``caps``/``default_cap``/``join_cap`` override this engine's table
        capacities for the restricted run — the demanded set is usually
        orders of magnitude smaller than the perfect model, and in a
        static-shape engine smaller tables are where pruning becomes speed.
        """
        q = as_query_literal(pred if args is None else (pred, args))
        if q.pred in self.db:  # EDB query: a pure selection
            rows = self.db[q.pred]
            return rows[query_row_mask(q, rows, None)]
        sub = self._query_engine(q, caps=caps, default_cap=default_cap,
                                 join_cap=join_cap).run()
        for k, v in sub.stats.items():
            # adorned/magic stats merge in (latest ask wins); never clobber
            # stats of predicates this engine materialized itself (the sub
            # aliases its restricted result under the original name)
            if k not in self.materialized:
                self.stats[k] = v
        info = sub._pred_info[sub.plan.query_pred]
        out = sub.query_agg(q.pred) if info.is_agg else sub.query(q.pred)
        if verify:
            self._verify_ask(q, out, info.is_agg)
        return out

    def ask_dense(self, pred: str, args: tuple, matmul=None,
                  sparse: bool | None = None, spmv=None):
        """Single-source fast path: lower a magic-restricted *decomposable*
        program onto a frontier semiring fixpoint seeded with the query
        frontier row (the dense analog of ``tc_decomposable``).

        Requires the canonical TC / shortest-path shape with the pivot (first)
        argument bound and everything else free; raises ``PlanError``
        otherwise.

        Two carriers behind the one lowering: the dense ``form="vector"``
        fixpoint (O(n²) per iteration) or the CSR-packed segment fixpoint
        (``core.sparse``, O(|E|) per iteration).  ``sparse`` (defaulting to
        ``PlanOptions.sparse``) forces a representation; ``None`` lets the
        density heuristic pick.  ``matmul`` overrides the dense ⊗, ``spmv``
        the sparse segment step.
        """
        low = detect_frontier_lowering(self.source_program, pred)
        q = as_query_literal((pred, args))
        src = frontier_query_source(q)
        if low is None or src is None:
            raise PlanError(
                f"query {q!r} does not admit the dense frontier lowering "
                "(need a decomposable TC/spath shape with the pivot bound)")
        edges = self.db[low.edb]
        if len(edges) == 0:  # no arcs -> nothing reachable
            rows = np.zeros((0, 2), np.int64)
            out = rows if low.kind == "bool" else (rows,
                                                   np.zeros((0,), np.int64))
            return out
        n = max(int(edges[:, :2].max()) + 1, src + 1)
        opts = self.plan.options
        use_csr = opts.sparse if sparse is None else sparse
        if use_csr is None:
            use_csr = _sparse.prefer_csr(
                len(edges), n,
                opts.sparse_threshold if opts.sparse_threshold is not None
                else _sparse.DEFAULT_SPARSE_THRESHOLD)
        if use_csr:
            if opts.tune:  # local import keeps core import-light
                from ..kernels import autotune as _at
                cfg = (opts.tune if isinstance(opts.tune, _at.KernelConfig)
                       else _at.autotune(edges, n, low.kind).config)
                csr = _at.build_tuned(edges, n, low.kind, cfg)
                if csr.plan_cfg is not None and spmv is None:
                    from ..kernels import ops as _kops
                    spmv = _kops.csr_frontier_step(low.kind)
            else:
                csr = _sparse.build_csr(edges, n, low.kind)
            init = _sparse.rows_from_sources(csr, [src])
            res = _sparse.fixpoint_csr_cached(csr, init, spmv=spmv)
            row = np.asarray(res.table[0])
        elif low.kind == "bool":
            adj = np.zeros((n, n), bool)
            adj[edges[:, 0], edges[:, 1]] = True
            res = reachable_from_dense(jnp.asarray(adj), src, matmul=matmul)
            row = np.asarray(res.table)
        else:
            w = np.full((n, n), np.inf, np.float32)
            np.minimum.at(w, (edges[:, 0], edges[:, 1]), edges[:, 2].astype(np.float32))
            res = single_source_distances_dense(jnp.asarray(w), src,
                                                matmul=matmul)
            row = np.asarray(res.table)
        if low.kind == "bool":
            dst = np.nonzero(row[:n])[0]
            out = np.stack([np.full(len(dst), src, np.int64),
                            dst.astype(np.int64)], axis=1)
        else:
            dst = np.nonzero(np.isfinite(row[:n]))[0]
            rows = np.stack([np.full(len(dst), src, np.int64),
                             dst.astype(np.int64)], axis=1)
            out = (rows, row[dst].astype(np.int64))
        self.stats[f"{pred}__{'csr' if use_csr else 'dense'}"] = GroupStats(
            iterations=int(res.iterations), generated=int(res.generated))
        return out

    def ask_batch(self, queries: list | None = None, verify: bool = False,
                  caps: dict[str, int] | None = None,
                  default_cap: int | None = None,
                  join_cap: int | None = None) -> list:
        """Answer B queries, coalescing same-(pred, adornment)-shape groups
        into ONE tuple-path fixpoint via the qid-tagged magic rewrite.

        ``queries`` defaults to the program's own ``?-`` goals.  Answers come
        back in query order; each equals the corresponding ``ask()``.  Shapes
        that do not admit per-seed attribution (all-free adornments, packed-
        width overflow, non-magic plans) fall back to sequential ``ask()``.
        """
        specs = list(queries) if queries is not None else \
            list(self.source_program.queries)
        qlits = [as_query_literal(s) for s in specs]
        out: list = [None] * len(qlits)
        kw = dict(caps=caps, default_cap=default_cap, join_cap=join_cap)
        groups: dict[tuple[str, str], list[int]] = {}
        for i, q in enumerate(qlits):
            if q.pred in self.db:  # EDB query: a pure selection
                rows = self.db[q.pred]
                out[i] = rows[query_row_mask(q, rows, None)]
                continue
            adn = batch_adornment(self.source_program, q)
            groups.setdefault((q.pred, adn), []).append(i)
        verify_full = None  # ONE full-model engine checks the whole batch
        for (pred, adn), idxs in groups.items():
            res = None
            if len(idxs) > 1 and "b" in adn and self.magic:
                res = self._try_batch([qlits[i] for i in idxs], **kw)
            if res is None:
                res = [self.ask(qlits[i], verify=verify, **kw) for i in idxs]
            elif verify:
                info_agg = self._batch_is_agg(pred)
                if verify_full is None:
                    verify_full = Engine(
                        self.source_program, db=self.db, bits=self.bits,
                        caps=self.caps, default_cap=self.default_cap,
                        join_cap=self.join_cap, max_iters=self.max_iters).run()
                for i, r in zip(idxs, res):
                    self._verify_ask(qlits[i], r, info_agg, full=verify_full)
            for i, r in zip(idxs, res):
                out[i] = r
        return out

    def _batch_is_agg(self, pred: str) -> bool:
        return any(r.agg is not None
                   for r in self.source_program.rules_for(pred))

    def _try_batch(self, batch: list[Literal], caps=None, default_cap=None,
                   join_cap=None) -> list | None:
        """One qid-tagged fixpoint for a same-shape batch, or None when the
        shape must evaluate sequentially (not batchable / won't pack / table
        overflow under the union of demands)."""
        try:
            sub = Engine(self.source_program, db=self.db, bits=self.bits,
                         caps=self.caps if caps is None else caps,
                         default_cap=default_cap or self.default_cap,
                         join_cap=join_cap or self.join_cap,
                         max_iters=self.max_iters, batch=batch,
                         **self._opt_kwargs())
            sub.run()
        except (PlanError, MagicError, ValueError, CapacityError):
            # ValueError covers packed-width overflow (qid column pushes the
            # schema past 62 bits) and out-of-domain seed constants
            return None
        for k, v in sub.stats.items():
            if k not in self.materialized:
                self.stats[k] = v
        return sub.batch_results()

    def batch_results(self) -> list:
        """Per-query answers of a batch-planned engine, in batch order."""
        if self._batch_out is None:
            raise RuntimeError("engine has no batch plan or run() not called")
        return self._batch_out

    def _finalize_batch(self):
        """Split the qid-tagged query predicate into per-query answers
        (:func:`split_qid_answers`)."""
        qp = self.plan.query_pred
        info = self._pred_info[qp]
        rows, vals = self.materialized.get(
            qp, (np.zeros((0, info.key_arity), np.int64), None))
        self._batch_out = split_qid_answers(
            qp, rows, vals, info, self.plan.options.batch)

    def _opt_kwargs(self) -> dict:
        """Representation/bucketing options to thread into sub-engines."""
        opts = self.plan.options
        return dict(sparse=opts.sparse, sparse_threshold=opts.sparse_threshold,
                    bucket_floors=dict(opts.bucket_floors), tune=opts.tune)

    def _query_engine(self, q: Literal, caps=None, default_cap=None,
                      join_cap=None) -> "Engine":
        kwargs = dict(db=self.db, bits=self.bits,
                      caps=self.caps if caps is None else caps,
                      default_cap=default_cap or self.default_cap,
                      join_cap=join_cap or self.join_cap,
                      max_iters=self.max_iters, **self._opt_kwargs())
        try:
            return Engine(self.source_program, query=q, magic=self.magic, **kwargs)
        except PlanError:
            # magic bodies the join planner cannot order (e.g. cartesian
            # magic prefixes) fall back to demanded-strata + residual filter
            return Engine(self.source_program, query=q, magic=False, **kwargs)

    def _verify_ask(self, q: Literal, got, is_agg: bool, full: "Engine | None" = None):
        if full is None:
            if q.pred in self.materialized:
                full = self
            else:
                full = Engine(self.source_program, db=self.db, bits=self.bits,
                              caps=self.caps, default_cap=self.default_cap,
                              join_cap=self.join_cap,
                              max_iters=self.max_iters).run()
        info = full._pred_info[q.pred]
        if is_agg:
            rows, vals = full.query_agg(q.pred)
            mask = query_row_mask(q, rows, vals, info)
            want = {(*map(int, r), int(v)) for r, v in zip(rows[mask], vals[mask])}
            have = {(*map(int, r), int(v)) for r, v in zip(got[0], got[1])}
        else:
            rows = full.query(q.pred)
            mask = query_row_mask(q, rows, None, info)
            want = {tuple(map(int, r)) for r in rows[mask]}
            have = {tuple(map(int, r)) for r in got}
        if want != have:
            raise AssertionError(
                f"ask({q!r}) disagrees with the full-model path: "
                f"missing={sorted(want - have)[:5]} extra={sorted(have - want)[:5]}")

    def _finalize_query(self):
        """Restrict the query predicate's result by the query constants and
        alias it (materialization + stats) under the original name.

        Every constant of the query goal filters here — bound positions
        included: the magic rewrite restricts evaluation to the *demanded*
        set, which can legitimately exceed the queried set (e.g. ``sg``
        demands its ancestors' generations en route to the query's own).
        """
        qp = self.plan.query_pred
        orig = self.plan.aliases.get(qp, qp)
        if qp not in self.materialized:
            return
        rows, vals = self.materialized[qp]
        info = self._pred_info[qp]
        q = self.plan.options.query
        if q is not None:
            mask = query_row_mask(q, rows, vals, info)
        else:
            mask = np.ones(len(rows), bool)
            for pos, c in self.plan.residual_filters:
                if info.is_agg and pos == info.agg_pos:
                    mask &= np.asarray(vals) == c
                else:
                    mask &= np.asarray(rows[:, info.key_rank(pos)]) == c
        if not mask.all():
            rows = rows[mask]
            vals = vals[mask] if vals is not None else None
        self.materialized[qp] = (rows, vals)
        self.materialized[orig] = self.materialized[qp]
        self.stats[orig] = self.stats[qp]

    def _result(self, pred: str):
        if pred not in self.materialized:
            raise KeyError(f"{pred} not evaluated; call run() (known: {list(self.materialized)})")
        return self.materialized[pred]

    def invalidate(self, rel: str | None = None) -> "Engine":
        """Reset evaluated state so ``run()`` re-evaluates from current data.

        Drops materialized results/stats and cached indexes over them; with
        ``rel``, also drops indexes/scans of that relation (its rows changed
        — e.g. a serving-layer seed swap or monotone append).  Base-EDB
        indexes otherwise persist across runs.
        """
        self.materialized.clear()
        self.stats.clear()
        self._index_cache = {
            k: v for k, v in self._index_cache.items()
            if k[0] in self.db and (rel is None or k[0] != rel)}
        return self

    # -- plumbing --------------------------------------------------------------

    def _rows_of(self, rel: str) -> np.ndarray:
        if rel in self.db:
            return self.db[rel]
        if rel in self.materialized:
            rows, vals = self.materialized[rel]
            if vals is not None:
                # re-insert the aggregate value at its literal position
                pos = self._pred_info[rel].agg_pos
                return np.concatenate(
                    [rows[:, :pos], vals[:, None].astype(np.int64), rows[:, pos:]],
                    axis=1)
            return rows
        raise PlanError(f"unknown relation {rel!r} (neither EDB nor evaluated IDB)")

    def _bucket_floor(self, rel: str) -> int:
        """Per-relation quantize_rows floor (``PlanOptions.bucket_floors``)."""
        for name, floor in self.plan.options.bucket_floors:
            if name == rel:
                return floor
        return 8

    def _index(self, rel: str, cols: tuple[int, ...]) -> EdbIndex:
        key = (rel, cols)
        if key not in self._index_cache:
            self._index_cache[key] = build_edb_index(
                self._rows_of(rel), cols, self.bits,
                minimum=self._bucket_floor(rel))
        return self._index_cache[key]

    def _schema(self, info) -> Schema:
        return Schema(tuple([self.bits] * info.key_arity))

    def _cap(self, pred: str) -> int:
        if pred in self.caps:
            return self.caps[pred]
        # adorned (tc__bf) and magic (m__tc__bf) predicates inherit the
        # original predicate's capacity so caps= keeps working under ask()
        orig = self.plan.aliases.get(pred)
        if orig is not None and orig in self.caps:
            return self.caps[orig]
        return self.default_cap

    # -- group evaluation -----------------------------------------------------

    def _gather_edb(self, gp: GroupPlan):
        """Collect every EDB input the group's pipelines read — join indexes
        and (pre-selected) source rows — as concrete arrays.  These are jit
        *arguments* of the group runner, never trace-time constants, so
        compiled fixpoints stay valid across changing data (incremental
        appends, different magic seeds)."""
        idx: dict[tuple, EdbIndex] = {}
        src: dict[tuple, tuple[jax.Array, jax.Array]] = {}
        for cr in gp.exit_rules + gp.rec_rules:
            if isinstance(cr.source, SourceEdb):
                key = (cr.source.rel, cr.source.select)
                if key not in src:
                    src[key] = self._source_rows(cr.source)
            for step in cr.joins:
                if isinstance(step, EdbJoinStep):
                    idx[(step.rel, step.build_cols)] = \
                        self._index(step.rel, step.build_cols)
        return {"idx": idx, "src": src}

    def _source_rows(self, source: SourceEdb):
        np_rows = self._rows_of(source.rel)
        for col, const in source.select:  # pushed-down selections
            np_rows = np_rows[np.asarray(np_rows[:, col]) == const]
        n = len(np_rows)
        # bucket data-dependent scan shapes (per-relation floors pin shapes)
        cap = quantize_rows(max(n, 1), minimum=max(self._bucket_floor(source.rel), 8))
        if cap > n:
            pad = np.zeros((cap - n, self._rows_of(source.rel).shape[1]), np.int64)
            np_rows = np.concatenate([np.asarray(np_rows, np.int64), pad])
        valid = jnp.arange(cap) < n
        return jnp.asarray(np_rows), valid

    def _gather_facts(self, gp: GroupPlan):
        """Pack the group's fact rows (incl. magic seed facts) per predicate.
        Packed keys are jit arguments, so queries differing only in their
        seed constants share one compiled runner.  Warm-start rows (a
        previously materialized monotone model, see ``run(warm=)``) merge in
        as extra facts: the fixpoint re-enters from that lower bound."""
        limit = (1 << self.bits) - 1
        out = {}
        for pred, info in gp.preds.items():
            facts = [r for r in self.program.rules_for(pred) if r.is_fact()]
            if facts:
                rows = np.array([[a.value for a in r.head.args] for r in facts], np.int64)
                key_cols = [i for i in range(rows.shape[1])
                            if not (info.is_agg and i == info.agg_pos)]
                kv = rows[:, key_cols]
                if kv.size and (kv.min() < 0 or kv.max() > limit):
                    raise ValueError(
                        f"fact/query constant for {pred!r} exceeds the "
                        f"{self.bits}-bit packed domain (packing would "
                        f"silently truncate)")
                out[pred] = self._pack_rows(rows, info)
            if pred in self._warm:
                wrows, wvals = self._warm[pred]
                init = None
                if info.is_agg:
                    init = _MERGE_INIT["min" if info.agg == "min" else
                                       "max" if info.agg == "max" else "sum"]
                wk, wv = pack_warm_rows(wrows, wvals, self._schema(info), init)
                if pred in out:
                    fk, fv = out[pred]
                    wk = jnp.concatenate([fk, wk])
                    wv = jnp.concatenate([fv, wv]) if wv is not None else None
                out[pred] = (wk, wv)
        return out

    def _eval_group(self, gp: GroupPlan):
        edb = self._gather_edb(gp)
        facts = self._gather_facts(gp)
        ex = GroupExecutor(
            gp, caps={p: self._cap(p) for p in gp.preds}, bits=self.bits,
            jcap=self.join_cap or self.default_cap, max_iters=self.max_iters)
        state, iters, gen = ex.runner()(facts, edb)

        # materialize + overflow check, register for later strata
        for pred, info in gp.preds.items():
            t = state[pred]["all"]
            if bool(t.overflow):
                raise CapacityError(
                    f"relation {pred!r} overflowed capacity {self._cap(pred)}; "
                    f"pass caps={{'{pred}': <larger>}}"
                )
            schema = self._schema(info)
            if info.is_agg:
                rows, vals = t.to_numpy(schema)
                self.materialized[pred] = (rows, vals)
            else:
                self.materialized[pred] = (t.to_numpy(schema), None)
            self.stats[pred] = GroupStats(iterations=int(iters), generated=int(gen))

    def _pack_rows(self, rows: np.ndarray, info):
        schema = self._schema(info)
        if info.is_agg:
            keys = schema.pack([jnp.asarray(rows[:, i]) for i in range(info.key_arity)])
            vals = jnp.asarray(rows[:, info.key_arity], jnp.int32)
            return keys, vals
        keys = schema.pack([jnp.asarray(rows[:, i]) for i in range(rows.shape[1])])
        return keys, None
