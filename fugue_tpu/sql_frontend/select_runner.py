"""SELECT executor over DataFrames on pandas — the role qpd plays for the
reference's native engine (reference fugue/execution/native_execution_engine.py:41-65)
and duckdb plays for its SQL backends.

Executes the AST from :mod:`fugue_tpu.sql_frontend.parser` with SQL
semantics: three-valued logic, null-ignoring aggregates, null keys never
joining, null-safe set operations.
"""

from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from fugue_tpu.exceptions import FugueSQLRuntimeError
from fugue_tpu.dataframe import DataFrame, DataFrames
from fugue_tpu.dataframe.arrow_dataframe import ArrowDataFrame
from fugue_tpu.dataframe.dataframe import LocalBoundedDataFrame
from fugue_tpu.column.functions import (
    VARIANCE_FUNCS,
    variance_ddof,
    variance_stat,
)
from fugue_tpu.column.pandas_eval import compile_like_regex, sql_fmod
from fugue_tpu.schema import Schema
from fugue_tpu.sql_frontend import ast
from fugue_tpu.sql_frontend.parser import parse_select

__all__ = ["run_select", "run_query", "SQLExecutionError"]


class SQLExecutionError(FugueSQLRuntimeError, ValueError):
    """SQL execution failure (ValueError kept for pre-hierarchy
    callers)."""


def run_select(sql: str, dfs: DataFrames) -> LocalBoundedDataFrame:
    """Parse and execute ``sql`` against the named dataframes in ``dfs``."""
    return run_query(parse_select(sql), dfs)


def run_query(query: ast.Query, dfs: DataFrames) -> LocalBoundedDataFrame:
    env: Dict[str, "_Table"] = {}
    for name, df in dfs.items():
        env[name.lower()] = _Table.from_fugue(df)
    res = _run(query, env)
    return res.to_fugue()


# ---- typed columnar intermediates ---------------------------------------


class _TS(NamedTuple):
    """A typed series: values aligned to the current scope index + the
    arrow output type (None = not yet determined)."""

    series: pd.Series
    dtype: Optional[pa.DataType]


class _Table:
    """An executed relation: pandas frame with output names + arrow types."""

    def __init__(self, frame: pd.DataFrame, names: List[str],
                 types: List[Optional[pa.DataType]]):
        self.frame = frame
        self.names = names
        self.types = types

    @staticmethod
    def from_fugue(df: DataFrame) -> "_Table":
        pdf = df.as_pandas().reset_index(drop=True)
        schema = df.schema
        pdf.columns = list(range(len(schema)))
        return _Table(pdf, list(schema.names), list(schema.types))

    def to_fugue(self) -> LocalBoundedDataFrame:
        arrays: List[pa.Array] = []
        fields: List[pa.Field] = []
        for i, (name, tp) in enumerate(zip(self.names, self.types)):
            s = self.frame.iloc[:, i] if self.frame.shape[1] > i else \
                pd.Series([], dtype=object)
            arr = _series_to_arrow(s, tp)
            arrays.append(arr)
            fields.append(pa.field(name, arr.type))
        table = pa.Table.from_arrays(arrays, schema=pa.schema(fields))
        return ArrowDataFrame(table)


def _series_to_arrow(s: pd.Series, tp: Optional[pa.DataType]) -> pa.Array:
    target = tp if tp is not None and not pa.types.is_null(tp) else None
    try:
        if target is not None:
            return pa.Array.from_pandas(s, type=target)
        arr = pa.Array.from_pandas(s)
        if pa.types.is_null(arr.type):
            return arr.cast(pa.string())
        return arr
    except (pa.ArrowInvalid, pa.ArrowTypeError, pa.ArrowNotImplementedError):
        arr = pa.Array.from_pandas(s.astype(object).where(s.notna(), None))
        if target is not None:
            return arr.cast(target)
        return arr


# ---- scopes -------------------------------------------------------------


class _Entry(NamedTuple):
    qual: Optional[str]  # lower-cased table alias/name
    name: str
    label: Any  # column label in the scope frame
    dtype: Optional[pa.DataType]


class _Scope:
    def __init__(self, frame: pd.DataFrame, entries: List[_Entry]):
        self.frame = frame
        self.entries = entries

    @staticmethod
    def from_table(t: _Table, qual: Optional[str]) -> "_Scope":
        frame = t.frame.copy(deep=False)
        labels = [f"c{i}" for i in range(len(t.names))]
        frame.columns = labels
        q = qual.lower() if qual is not None else None
        entries = [
            _Entry(q, n, lb, tp)
            for n, lb, tp in zip(t.names, labels, t.types)
        ]
        return _Scope(frame, entries)

    def candidates(self, name: str, qual: Optional[str]) -> List[_Entry]:
        """Exact-name matches, else case-insensitive matches (SQL
        identifier folding). 0 = not found, >1 = ambiguous."""
        q = qual.lower() if qual is not None else None
        cands = [
            e for e in self.entries
            if e.name == name and (q is None or e.qual == q)
        ]
        if len(cands) == 0:  # case-insensitive fallback
            low = name.lower()
            cands = [
                e for e in self.entries
                if e.name.lower() == low and (q is None or e.qual == q)
            ]
        return cands

    def resolve(self, name: str, qual: Optional[str]) -> _Entry:
        cands = self.candidates(name, qual)
        if len(cands) == 0:
            raise SQLExecutionError(f"column not found: {_qname(name, qual)}")
        if len(cands) > 1:
            raise SQLExecutionError(f"ambiguous column: {_qname(name, qual)}")
        return cands[0]

    def star_entries(self, qual: Optional[str]) -> List[_Entry]:
        if qual is None:
            return list(self.entries)
        q = qual.lower()
        out = [e for e in self.entries if e.qual == q]
        if len(out) == 0:
            raise SQLExecutionError(f"unknown table {qual!r} in wildcard")
        return out


def _qname(name: str, qual: Optional[str]) -> str:
    return name if qual is None else f"{qual}.{name}"


# ---- query execution ----------------------------------------------------


def _run(query: ast.Query, env: Dict[str, _Table]) -> _Table:
    if isinstance(query, ast.With):
        scoped = dict(env)
        for name, sub in query.ctes:
            scoped[name.lower()] = _run(sub, scoped)
        return _run(query.body, scoped)
    if isinstance(query, ast.SetOp):
        return _run_setop(query, env)
    if isinstance(query, ast.Select):
        return _run_select(query, env)
    raise SQLExecutionError(f"unsupported query node {type(query).__name__}")


def _lookup_table(name: str, env: Dict[str, _Table]) -> _Table:
    t = env.get(name.lower())
    if t is None:
        raise SQLExecutionError(f"table not found: {name}")
    return t


def _build_scope(rel: ast.Relation, env: Dict[str, _Table]) -> _Scope:
    if isinstance(rel, ast.TableRef):
        t = _lookup_table(rel.name, env)
        return _Scope.from_table(t, rel.alias or rel.name)
    if isinstance(rel, ast.SubqueryRef):
        return _Scope.from_table(_run(rel.query, env), rel.alias)
    if isinstance(rel, ast.JoinRel):
        left = _build_scope(rel.left, env)
        right = _build_scope(rel.right, env)
        return _join_scopes(left, right, rel, env)
    raise SQLExecutionError(f"unsupported relation {type(rel).__name__}")


def _relabel(scope: _Scope, prefix: str) -> _Scope:
    mapping = {e.label: f"{prefix}{e.label}" for e in scope.entries}
    frame = scope.frame.rename(columns=mapping)
    entries = [e._replace(label=mapping[e.label]) for e in scope.entries]
    return _Scope(frame, entries)


def _join_scopes(
    left: _Scope, right: _Scope, rel: ast.JoinRel,
    env: Optional[Dict[str, _Table]] = None,
) -> _Scope:
    left = _relabel(left, "l_")
    right = _relabel(right, "r_")
    how = rel.how
    if how == "cross":
        frame = left.frame.merge(right.frame, how="cross")
        return _Scope(frame, left.entries + right.entries)
    # extract equi-join key expressions
    pairs: List[Tuple[_TS, _TS]] = []
    residual: Optional[ast.Expr] = None
    coalesce_pairs: List[Tuple[Any, Any]] = []  # (left label, right label)
    hidden_right: List[Any] = []
    if rel.using is not None:
        for name in rel.using:
            le = left.resolve(name, None)
            re_ = right.resolve(name, None)
            pairs.append((
                _TS(left.frame[le.label], le.dtype),
                _TS(right.frame[re_.label], re_.dtype),
            ))
            coalesce_pairs.append((le.label, re_.label))
            hidden_right.append(re_.label)
    elif rel.on is not None:
        conj = _split_conjunction(rel.on)
        ev_l, ev_r = _Evaluator(left, env=env), _Evaluator(right, env=env)
        for c in conj:
            sides = _equi_sides(c, ev_l, ev_r)
            if sides is None:
                residual = c if residual is None else \
                    ast.Binary("AND", residual, c)
            else:
                pairs.append(sides)
        if len(pairs) == 0:
            if how != "inner":
                raise SQLExecutionError(
                    f"{how} join requires at least one equi-join condition"
                )
            frame = left.frame.merge(right.frame, how="cross")
            scope = _Scope(frame, left.entries + right.entries)
            if rel.on is not None:
                mask = _to_bool_mask(
                    _Evaluator(scope, env=env).eval(rel.on).series
                )
                scope = _Scope(scope.frame[mask], scope.entries)
            return scope
    else:
        raise SQLExecutionError("join requires ON or USING")
    lf = left.frame.copy(deep=False)
    rf = right.frame.copy(deep=False)
    keys = []
    for i, (lts, rts) in enumerate(pairs):
        k = f"_jk{i}"
        lf[k] = lts.series
        rf[k] = rts.series
        keys.append(k)
    from fugue_tpu.execution.native_execution_engine import _pandas_join

    how_map = {
        "inner": "inner", "left_outer": "leftouter",
        "right_outer": "rightouter", "full_outer": "fullouter",
        "semi": "semi", "anti": "anti",
    }
    joined = _pandas_join(lf, rf, how_map[how], keys)
    entries = list(left.entries)
    if how in ("semi", "anti"):
        joined = joined[[e.label for e in left.entries]]
    else:
        for ll, rl in coalesce_pairs:
            # USING: expose one coalesced key column under the left label
            if how in ("right_outer", "full_outer"):
                joined[ll] = joined[ll].combine_first(joined[rl])
        entries = entries + [
            e for e in right.entries if e.label not in hidden_right
        ]
        joined = joined[[e.label for e in entries]]
    scope = _Scope(joined.reset_index(drop=True), entries)
    if residual is not None:
        mask = _to_bool_mask(
            _Evaluator(scope, env=env).eval(residual).series
        )
        scope = _Scope(scope.frame[mask].reset_index(drop=True), scope.entries)
    return scope


def _split_conjunction(e: ast.Expr) -> List[ast.Expr]:
    if isinstance(e, ast.Binary) and e.op == "AND":
        return _split_conjunction(e.left) + _split_conjunction(e.right)
    return [e]


def _equi_sides(
    e: ast.Expr, ev_l: "_Evaluator", ev_r: "_Evaluator"
) -> Optional[Tuple[_TS, _TS]]:
    """If ``e`` is ``left_expr = right_expr`` (each side evaluable on one
    scope), evaluate both; else None."""
    if not (isinstance(e, ast.Binary) and e.op == "="):
        return None
    for a, b in ((e.left, e.right), (e.right, e.left)):
        try:
            lts = ev_l.eval(a)
        except SQLExecutionError:
            continue
        try:
            rts = ev_r.eval(b)
        except SQLExecutionError:
            continue
        return lts, rts
    return None


def _to_bool_mask(s: pd.Series) -> np.ndarray:
    return s.astype("boolean").fillna(False).to_numpy(dtype=bool)


# ---- expression evaluation ----------------------------------------------

_NUMERIC = (pa.int64(), pa.float64())


def _is_float(tp: Optional[pa.DataType]) -> bool:
    return tp is not None and pa.types.is_floating(tp)


def _arith_type(
    op: str, lt: Optional[pa.DataType], rt: Optional[pa.DataType]
) -> pa.DataType:
    if op == "/":
        return pa.float64()
    if _is_float(lt) or _is_float(rt):
        return pa.float64()
    if lt is not None and rt is not None and \
            pa.types.is_integer(lt) and pa.types.is_integer(rt):
        return pa.int64()
    return pa.float64()


def _walk_nodes(n: ast.Node, fn: Callable[[ast.Node], None]) -> None:
    fn(n)
    for f in n._fields:
        _walk_val(getattr(n, f), fn)


def _walk_val(v: Any, fn: Callable[[ast.Node], None]) -> None:
    if isinstance(v, ast.Node):
        _walk_nodes(v, fn)
    elif isinstance(v, (list, tuple)):
        for x in v:
            _walk_val(x, fn)


def _transform(n: ast.Node, tr: Callable[[ast.Node], Optional[ast.Node]]) -> Any:
    r = tr(n)
    if r is not None:
        return r
    return type(n)(*[_transform_val(getattr(n, f), tr) for f in n._fields])


def _transform_val(v: Any, tr: Callable[[ast.Node], Optional[ast.Node]]) -> Any:
    if isinstance(v, ast.Node):
        return _transform(v, tr)
    if isinstance(v, list):
        return [_transform_val(x, tr) for x in v]
    if isinstance(v, tuple):
        return tuple(_transform_val(x, tr) for x in v)
    return v


def _static_output_names(
    q: ast.Query, env_names: Dict[str, List[str]], ctes: Dict[str, List[str]]
) -> List[str]:
    """Best-effort output column names of a query WITHOUT executing it
    (for correlation analysis; unknown pieces expand to nothing)."""
    if isinstance(q, ast.With):
        scoped = dict(ctes)
        for name, sub in q.ctes:
            scoped[name.lower()] = _static_output_names(sub, env_names, scoped)
        return _static_output_names(q.body, env_names, scoped)
    if isinstance(q, ast.SetOp):
        return _static_output_names(q.left, env_names, ctes)
    if not isinstance(q, ast.Select):
        return []
    out: List[str] = []
    for i, item in enumerate(q.items):
        if isinstance(item.expr, ast.Star):
            rel = q.from_
            if rel is not None:
                names: List[str] = []

                def visit(n: ast.Node) -> None:
                    if isinstance(n, ast.TableRef):
                        src = ctes.get(n.name.lower()) or env_names.get(
                            n.name.lower()
                        )
                        if src:
                            names.extend(src)
                    elif isinstance(n, ast.SubqueryRef):
                        names.extend(
                            _static_output_names(n.query, env_names, ctes)
                        )

                _walk_nodes(rel, visit)
                out.extend(names)
        else:
            out.append(_output_name(item, i))
    return out


def _outer_refs(
    q: ast.Query, env: Dict[str, "_Table"], outer_scope: "_Scope"
) -> List[ast.Col]:
    """Column references inside ``q`` that do not bind to ANY name
    visible inside the subquery subtree (union-of-subtree name sets;
    unqualified names prefer inner binding, matching SQL's
    innermost-first rule) but DO resolve in the enclosing scope."""
    env_names = {k: list(t.names) for k, t in env.items()}
    quals: Set[str] = set()
    cols: Set[str] = set()
    ctes: Dict[str, List[str]] = {}

    def gather(n: ast.Node) -> None:
        if isinstance(n, ast.With):
            for name, sub in n.ctes:
                ctes[name.lower()] = _static_output_names(
                    sub, env_names, ctes
                )
                quals.add(name.lower())
        elif isinstance(n, ast.TableRef):
            alias = (n.alias or n.name).lower()
            quals.add(alias)
            src = ctes.get(n.name.lower()) or env_names.get(
                n.name.lower()
            ) or []
            cols.update(x.lower() for x in src)
        elif isinstance(n, ast.SubqueryRef):
            quals.add(n.alias.lower())
            cols.update(
                x.lower()
                for x in _static_output_names(n.query, env_names, ctes)
            )
        elif isinstance(n, ast.SelectItem) and n.alias is not None:
            # select aliases count as inner names so ORDER BY/GROUP BY
            # alias refs inside the subquery are never substituted.
            # Known limit: an unqualified OUTER ref colliding with an
            # inner select alias binds nowhere and errors (this engine
            # never resolves aliases in WHERE, subquery or not)
            cols.add(n.alias.lower())

    _walk_nodes(q, gather)
    found: List[ast.Col] = []
    seen: Set[Tuple[Optional[str], str]] = set()

    def classify(n: ast.Node) -> None:
        if not isinstance(n, ast.Col):
            return
        tl = n.table.lower() if n.table is not None else None
        key = (tl, n.name.lower())
        if key in seen:
            return
        if tl is not None:
            if tl in quals:
                return
        elif n.name.lower() in cols:
            return
        try:
            outer_scope.resolve(n.name, n.table)
        except Exception:
            return
        seen.add(key)
        found.append(ast.Col(n.name, n.table))

    _walk_nodes(q, classify)
    return found


def _subst_outer(
    q: ast.Query, refs: List[ast.Col], values: Tuple[Any, ...]
) -> ast.Query:
    """Rebuild the subquery with every outer reference replaced by the
    current outer row's value as a literal."""
    mapping = {
        (
            r.table.lower() if r.table is not None else None,
            r.name.lower(),
        ): v
        for r, v in zip(refs, values)
    }

    def tr(n: ast.Node) -> Optional[ast.Node]:
        if isinstance(n, ast.Col):
            key = (
                n.table.lower() if n.table is not None else None,
                n.name.lower(),
            )
            if key in mapping:
                return ast.Lit(mapping[key])
        return None

    return _transform(q, tr)


class _Evaluator:
    """Evaluates expressions over a scope with SQL null semantics.
    ``env`` (the visible tables) enables subquery expressions; outer
    references inside them correlate to this evaluator's scope."""

    def __init__(
        self,
        scope: _Scope,
        allow_agg: bool = False,
        env: Optional[Dict[str, _Table]] = None,
    ):
        self.scope = scope
        self.allow_agg = allow_agg
        self.env = env

    @property
    def index(self) -> pd.Index:
        return self.scope.frame.index

    def const(self, value: Any, dtype: Optional[pa.DataType]) -> _TS:
        return _TS(pd.Series([value] * len(self.index), index=self.index,
                             dtype=object if value is None else None),
                   dtype)

    def eval(self, e: ast.Expr) -> _TS:
        if isinstance(e, ast.Lit):
            v = e.value
            if v is None:
                return self.const(None, None)
            if isinstance(v, bool):
                return self.const(v, pa.bool_())
            if isinstance(v, int):
                return self.const(v, pa.int64())
            if isinstance(v, float):
                return self.const(v, pa.float64())
            return self.const(v, pa.string())
        if isinstance(e, ast.Col):
            entry = self.scope.resolve(e.name, e.table)
            return _TS(self.scope.frame[entry.label], entry.dtype)
        if isinstance(e, ast.Unary):
            return self._unary(e)
        if isinstance(e, ast.Binary):
            return self._binary(e)
        if isinstance(e, ast.IsNull):
            ts = self.eval(e.operand)
            res = ts.series.isna()
            if e.negated:
                res = ~res
            return _TS(res.astype("boolean"), pa.bool_())
        if isinstance(e, ast.InList):
            return self._in_list(e)
        if isinstance(e, ast.Between):
            low = ast.Binary("<=", e.operand, e.high)
            high = ast.Binary(">=", e.operand, e.low)
            combined: ast.Expr = ast.Binary("AND", high, low)
            if e.negated:
                combined = ast.Unary("NOT", combined)
            return self.eval(combined)
        if isinstance(e, ast.Like):
            return self._like(e)
        if isinstance(e, ast.Case):
            return self._case(e)
        if isinstance(e, ast.Cast):
            return self._cast(e)
        if isinstance(e, ast.Func):
            return self._func(e)
        if isinstance(e, ast.Window):
            return _eval_window(self, e)
        if isinstance(e, ast.ScalarSubquery):
            return self._scalar_subquery(e)
        if isinstance(e, ast.InSubquery):
            return self._in_subquery(e)
        if isinstance(e, ast.Exists):
            return self._exists(e)
        if isinstance(e, ast.Star):
            raise SQLExecutionError("wildcard not allowed in this context")
        raise SQLExecutionError(f"unsupported expression {type(e).__name__}")

    def _subquery_tables(
        self, q: ast.Query
    ) -> Tuple[Optional[_Table], Optional[List[_Table]]]:
        """Execute a subquery: uncorrelated -> (table, None), executed
        once; correlated -> (None, per-row tables), executed once per
        DISTINCT outer-reference tuple."""
        env = self.env if self.env is not None else {}
        refs = _outer_refs(q, env, self.scope)
        if not refs:
            return _run(q, env), None
        series = [self.eval(c).series for c in refs]
        cache: Dict[Tuple[Any, ...], _Table] = {}
        per_row: List[_Table] = []
        for i in range(len(self.index)):
            vals = []
            for s in series:
                v = s.iloc[i]
                if pd.isna(v):
                    v = None
                elif hasattr(v, "item"):
                    v = v.item()
                vals.append(v)
            key = tuple(vals)
            if key not in cache:
                q2 = _subst_outer(q, refs, key)
                cache[key] = _run(q2, env)
            per_row.append(cache[key])
        return None, per_row

    def _scalar_subquery(self, e: ast.ScalarSubquery) -> _TS:
        once, per_row = self._subquery_tables(e.query)

        def _value(t: _Table) -> Any:
            if len(t.names) != 1:
                raise SQLExecutionError(
                    "scalar subquery must return exactly one column"
                )
            if len(t.frame) > 1:
                raise SQLExecutionError(
                    "scalar subquery returned more than one row"
                )
            if len(t.frame) == 0:
                return None
            v = t.frame.iloc[0, 0]
            return None if pd.isna(v) else v

        if once is not None:
            return self.const(_value(once), once.types[0])
        assert per_row is not None
        tp = per_row[0].types[0] if per_row else None
        vals = [_value(t) for t in per_row]
        ser = pd.Series(vals, index=self.index)  # infers; None -> NaN
        return _TS(ser, tp)

    def _in_subquery(self, e: ast.InSubquery) -> _TS:
        ots = self.eval(e.operand)
        once, per_row = self._subquery_tables(e.query)

        def _membership(v: Any, t: _Table) -> Any:
            """SQL 3VL: match -> True; no match but NULLs present ->
            NULL; empty set -> False; NULL operand -> NULL unless the
            set is empty."""
            if len(t.names) != 1:
                raise SQLExecutionError(
                    "IN subquery must return exactly one column"
                )
            col = t.frame.iloc[:, 0]
            if len(col) == 0:
                return False
            if pd.isna(v):
                return None
            nn = col.dropna()
            hit = bool((nn == v).any()) if len(nn) else False
            if hit:
                return True
            return None if len(nn) < len(col) else False

        if once is not None:
            # vectorized path: one isin over the precomputed value set
            if len(once.names) != 1:
                raise SQLExecutionError(
                    "IN subquery must return exactly one column"
                )
            col = once.frame.iloc[:, 0]
            nn = col.dropna()
            has_null = len(nn) < len(col)
            if len(col) == 0:
                res = pd.Series(False, index=self.index).astype("boolean")
            else:
                hit = ots.series.isin(nn).astype("boolean")
                if has_null:
                    hit[~hit.fillna(False).to_numpy(dtype=bool)] = pd.NA
                hit[ots.series.isna().to_numpy(dtype=bool)] = pd.NA
                res = hit
            if e.negated:
                res = ~res
            return _TS(res, pa.bool_())
        vals = []
        for i in range(len(self.index)):
            m = _membership(ots.series.iloc[i], per_row[i])  # type: ignore
            if e.negated and m is not None:
                m = not m
            vals.append(m)
        return _TS(
            pd.Series(vals, index=self.index, dtype=object).astype(
                "boolean"
            ),
            pa.bool_(),
        )

    def _exists(self, e: ast.Exists) -> _TS:
        once, per_row = self._subquery_tables(e.query)
        if once is not None:
            return self.const(len(once.frame) > 0, pa.bool_())
        assert per_row is not None
        vals = [len(t.frame) > 0 for t in per_row]
        return _TS(
            pd.Series(vals, index=self.index, dtype="boolean"), pa.bool_()
        )

    def _unary(self, e: ast.Unary) -> _TS:
        ts = self.eval(e.operand)
        if e.op == "NOT":
            return _TS(~ts.series.astype("boolean"), pa.bool_())
        if e.op == "-":
            return _TS(-pd.to_numeric(ts.series), ts.dtype or pa.float64())
        return ts  # unary +

    def _binary(self, e: ast.Binary) -> _TS:
        op = e.op
        if op in ("AND", "OR"):
            lb = self.eval(e.left).series.astype("boolean")
            rb = self.eval(e.right).series.astype("boolean")
            return _TS(lb & rb if op == "AND" else lb | rb, pa.bool_())
        lts = self.eval(e.left)
        rts = self.eval(e.right)
        if op in ("=", "<>", "<", "<=", ">", ">="):
            return self._compare(op, lts, rts)
        if op == "||":
            ls = lts.series.astype(object)
            rs = rts.series.astype(object)
            nulls = ls.isna() | rs.isna()
            res = ls.where(nulls, ls.astype(str) + rs.astype(str))
            res[nulls] = None
            return _TS(res, pa.string())
        left, right = lts.series, rts.series
        if op == "+":
            res = left + right
        elif op == "-":
            res = left - right
        elif op == "*":
            res = left * right
        elif op == "/":
            res = pd.to_numeric(left, errors="coerce").astype("float64") / \
                pd.to_numeric(right, errors="coerce")
        elif op == "%":
            res = sql_fmod(pd.to_numeric(left), pd.to_numeric(right))
        else:
            raise SQLExecutionError(f"unsupported operator {op}")
        return _TS(res, _arith_type(op, lts.dtype, rts.dtype))

    def _compare(self, op: str, lts: _TS, rts: _TS) -> _TS:
        left, right = lts.series, rts.series
        nulls = left.isna() | right.isna()
        func: Dict[str, Callable[[Any, Any], Any]] = {
            "=": lambda a, b: a == b,
            "<>": lambda a, b: a != b,
            "<": lambda a, b: a < b,
            "<=": lambda a, b: a <= b,
            ">": lambda a, b: a > b,
            ">=": lambda a, b: a >= b,
        }
        # compare only non-null positions: object-dtype series (e.g.
        # subquery results) would raise on None-vs-value otherwise
        res = pd.Series(pd.NA, index=left.index, dtype="boolean")
        m = (~nulls).to_numpy(dtype=bool)
        if m.any():
            with np.errstate(invalid="ignore"):
                r = func[op](left[m], right[m])
            res[m] = np.asarray(r, dtype=bool)
        return _TS(res, pa.bool_())

    def _in_list(self, e: ast.InList) -> _TS:
        ts = self.eval(e.operand)
        values = []
        for item in e.items:
            if not isinstance(item, ast.Lit):
                raise SQLExecutionError("IN list items must be literals")
            values.append(item.value)
        res = ts.series.isin([v for v in values if v is not None])
        res = res.astype("boolean")
        if e.negated:
            res = ~res
        res[ts.series.isna().to_numpy(dtype=bool)] = pd.NA
        return _TS(res, pa.bool_())

    def _like(self, e: ast.Like) -> _TS:
        ts = self.eval(e.operand)
        s = ts.series.astype(object)
        nulls = s.isna()
        if isinstance(e.pattern, ast.Lit):
            # the ONE anchored like->regex helper all three evaluators
            # share (device LUTs, pandas_eval, this runner): fullmatch
            # with \A...\Z — str.match + ^...$ would also accept a
            # trailing newline and silently diverge
            regex = compile_like_regex(str(e.pattern.value))
            matched = s.where(
                nulls, s.astype(str).str.fullmatch(regex, na=False)
            )
            res = matched.astype("boolean")
        else:
            # dynamic (column-valued) pattern: compile per DISTINCT
            # pattern value; NULL pattern -> NULL like any comparison
            p = self.eval(e.pattern).series
            nulls = nulls | p.isna()
            cache: Dict[Any, Any] = {}
            vals: List[Any] = []
            for v, pv in zip(s, p):
                if pd.isna(v) or pd.isna(pv):
                    vals.append(None)
                    continue
                rx = cache.get(pv)
                if rx is None:
                    rx = compile_like_regex(str(pv))
                    cache[pv] = rx
                vals.append(rx.fullmatch(str(v)) is not None)
            res = pd.Series(vals, index=s.index, dtype=object).astype(
                "boolean"
            )
        if e.negated:
            res = ~res
        res[nulls.to_numpy(dtype=bool)] = pd.NA
        return _TS(res, pa.bool_())

    def _case(self, e: ast.Case) -> _TS:
        whens = e.whens
        if e.operand is not None:
            whens = [
                (ast.Binary("=", e.operand, cond), val) for cond, val in whens
            ]
        default_ts = self.eval(e.default) if e.default is not None else \
            self.const(None, None)
        res = default_ts.series.astype(object)
        dtype = default_ts.dtype
        decided = pd.Series(False, index=self.index)
        for cond, val in whens:
            mask = _to_bool_mask(self.eval(cond).series) & ~decided.to_numpy()
            vts = self.eval(val)
            res = res.where(~mask, vts.series.astype(object))
            decided = decided | mask
            if dtype is None:
                dtype = vts.dtype
            elif vts.dtype is not None and not dtype.equals(vts.dtype):
                dtype = _arith_type("+", dtype, vts.dtype) \
                    if pa.types.is_integer(dtype) or pa.types.is_floating(dtype) \
                    else dtype
        return _TS(res, dtype)

    def _cast(self, e: ast.Cast) -> _TS:
        ts = self.eval(e.operand)
        tp = _SQL_TYPES.get(e.type_name)
        if tp is None:
            raise SQLExecutionError(f"unknown type {e.type_name}")
        s = ts.series
        try:
            if pa.types.is_integer(tp):
                num = pd.to_numeric(s, errors="raise")
                s = pd.Series(num, index=s.index).astype("Int64")
            elif pa.types.is_floating(tp):
                s = pd.to_numeric(s, errors="raise").astype("float64")
            elif pa.types.is_boolean(tp):
                s = s.map(_to_bool_scalar).astype("boolean")
            elif pa.types.is_string(tp):
                nulls = s.isna()
                s = s.astype(object)
                s = s.where(nulls, s.map(_to_str_scalar))
                s[nulls] = None
        except (ValueError, TypeError) as ex:
            raise SQLExecutionError(f"cast failed: {ex}") from ex
        return _TS(s, tp)

    def _func(self, e: ast.Func) -> _TS:
        name = e.name
        if name in _AGG_FUNCS:
            raise SQLExecutionError(
                f"aggregation {name} not allowed in this context"
            )
        impl = _SCALAR_FUNCS.get(name)
        if impl is None:
            raise SQLExecutionError(f"unsupported function {name}")
        args = [self.eval(a) for a in e.args]
        return impl(self, args)


def _to_bool_scalar(v: Any) -> Any:
    if v is None or (isinstance(v, float) and np.isnan(v)):
        return None
    if isinstance(v, str):
        return v.strip().lower() in ("true", "1", "t", "yes")
    return bool(v)


def _to_str_scalar(v: Any) -> str:
    if isinstance(v, bool):
        return "true" if v else "false"
    if isinstance(v, float) and v.is_integer():
        return str(v)
    return str(v)


_SQL_TYPES: Dict[str, pa.DataType] = {
    "int": pa.int32(), "integer": pa.int32(), "tinyint": pa.int8(),
    "smallint": pa.int16(), "bigint": pa.int64(), "long": pa.int64(),
    "float": pa.float32(), "real": pa.float32(),
    "double": pa.float64(), "decimal": pa.float64(), "numeric": pa.float64(),
    "string": pa.string(), "varchar": pa.string(), "char": pa.string(),
    "text": pa.string(),
    "boolean": pa.bool_(), "bool": pa.bool_(),
    "date": pa.date32(), "timestamp": pa.timestamp("us"),
    "datetime": pa.timestamp("us"),
    "binary": pa.binary(), "bytes": pa.binary(),
}


# ---- scalar function registry -------------------------------------------


def _fn_coalesce(ev: _Evaluator, args: List[_TS]) -> _TS:
    res = args[0].series
    dtype = args[0].dtype
    for a in args[1:]:
        res = res.combine_first(a.series)
        dtype = dtype or a.dtype
    return _TS(res, dtype)


def _fn_nullif(ev: _Evaluator, args: List[_TS]) -> _TS:
    a, b = args
    eq = _to_bool_mask(ev._compare("=", a, b).series)
    res = a.series.astype(object).where(~eq, None)
    return _TS(res, a.dtype)


def _fn_if(ev: _Evaluator, args: List[_TS]) -> _TS:
    cond, yes, no = args
    mask = _to_bool_mask(cond.series)
    res = yes.series.astype(object).where(mask, no.series.astype(object))
    return _TS(res, yes.dtype or no.dtype)


def _num_fn(f: Callable[[pd.Series], pd.Series],
            out: Optional[pa.DataType] = pa.float64()) -> Callable:
    def impl(ev: _Evaluator, args: List[_TS]) -> _TS:
        s = pd.to_numeric(args[0].series, errors="coerce")
        # out-of-domain inputs (SQRT(-4), LN(0)) yield NaN by SQL intent,
        # not as a numpy anomaly — keep -W error runs clean
        with np.errstate(invalid="ignore", divide="ignore"):
            res = f(s)
        return _TS(res, out if out is not None else args[0].dtype)
    return impl


def _fn_round(ev: _Evaluator, args: List[_TS]) -> _TS:
    s = pd.to_numeric(args[0].series, errors="coerce")
    digits = 0
    if len(args) > 1:
        digits = int(args[1].series.iloc[0]) if len(args[1].series) else 0
    return _TS(s.round(digits), pa.float64())


def _fn_power(ev: _Evaluator, args: List[_TS]) -> _TS:
    a = pd.to_numeric(args[0].series, errors="coerce")
    b = pd.to_numeric(args[1].series, errors="coerce")
    return _TS(a ** b, pa.float64())


def _fn_mod(ev: _Evaluator, args: List[_TS]) -> _TS:
    a = pd.to_numeric(args[0].series, errors="coerce")
    b = pd.to_numeric(args[1].series, errors="coerce")
    return _TS(sql_fmod(a, b), args[0].dtype or pa.int64())


def _str_fn(f: Callable[[pd.Series], pd.Series],
            out: pa.DataType = pa.string()) -> Callable:
    def impl(ev: _Evaluator, args: List[_TS]) -> _TS:
        s = args[0].series
        nulls = s.isna()
        res = f(s.astype(object).astype(str))
        res = pd.Series(res, index=s.index).astype(object)
        res[nulls.to_numpy(dtype=bool)] = None
        return _TS(res, out)
    return impl


def _fn_substring(ev: _Evaluator, args: List[_TS]) -> _TS:
    """Per-row 1-based start and optional length (standard SQL); NULL
    operand/start/length -> NULL. Shared helper with the column-algebra
    evaluator (``pandas_eval.sql_substring``)."""
    from fugue_tpu.column.pandas_eval import sql_substring

    starts = pd.to_numeric(args[1].series, errors="coerce")
    lens = (
        pd.to_numeric(args[2].series, errors="coerce")
        if len(args) > 2
        else None
    )
    return _TS(sql_substring(args[0].series, starts, lens), pa.string())


def _fn_concat(ev: _Evaluator, args: List[_TS]) -> _TS:
    res = None
    nulls = None
    for a in args:
        s = a.series
        nulls = s.isna() if nulls is None else (nulls | s.isna())
        part = s.astype(object).astype(str)
        res = part if res is None else res + part
    res = res.astype(object)
    res[nulls.to_numpy(dtype=bool)] = None
    return _TS(res, pa.string())


def _fn_replace(ev: _Evaluator, args: List[_TS]) -> _TS:
    s = args[0].series
    nulls = s.isna()
    old = str(args[1].series.iloc[0]) if len(args[1].series) else ""
    new = str(args[2].series.iloc[0]) if len(args[2].series) else ""
    res = s.astype(object).astype(str).str.replace(old, new, regex=False)
    res = res.astype(object)
    res[nulls.to_numpy(dtype=bool)] = None
    return _TS(res, pa.string())


_SCALAR_FUNCS: Dict[str, Callable[[ _Evaluator, List[_TS]], _TS]] = {
    "coalesce": _fn_coalesce,
    "nullif": _fn_nullif,
    "if": _fn_if,
    "iif": _fn_if,
    "abs": _num_fn(lambda s: s.abs(), None),
    "round": _fn_round,
    "floor": _num_fn(np.floor, pa.int64()),
    "ceil": _num_fn(np.ceil, pa.int64()),
    "ceiling": _num_fn(np.ceil, pa.int64()),
    "sqrt": _num_fn(np.sqrt),
    "exp": _num_fn(np.exp),
    "ln": _num_fn(np.log),
    "log": _num_fn(np.log),
    "log2": _num_fn(np.log2),
    "log10": _num_fn(np.log10),
    "sin": _num_fn(np.sin),
    "cos": _num_fn(np.cos),
    "tan": _num_fn(np.tan),
    "sign": _num_fn(np.sign, pa.int64()),
    "power": _fn_power,
    "pow": _fn_power,
    "mod": _fn_mod,
    "upper": _str_fn(lambda s: s.str.upper()),
    "ucase": _str_fn(lambda s: s.str.upper()),
    "lower": _str_fn(lambda s: s.str.lower()),
    "lcase": _str_fn(lambda s: s.str.lower()),
    "length": _str_fn(lambda s: s.str.len(), pa.int64()),
    "len": _str_fn(lambda s: s.str.len(), pa.int64()),
    "trim": _str_fn(lambda s: s.str.strip()),
    "ltrim": _str_fn(lambda s: s.str.lstrip()),
    "rtrim": _str_fn(lambda s: s.str.rstrip()),
    "reverse": _str_fn(lambda s: s.str[::-1]),
    "substring": _fn_substring,
    "substr": _fn_substring,
    "concat": _fn_concat,
    "replace": _fn_replace,
}


# ---- aggregation --------------------------------------------------------

_AGG_FUNCS = {
    "count", "sum", "avg", "mean", "min", "max", "first", "last",
    "first_value", "last_value", "stddev", "stddev_samp", "stddev_pop",
    "variance", "var_samp", "var_pop", "median",
}


def _contains_agg(e: ast.Expr) -> bool:
    if isinstance(e, ast.Window):
        # a window expression is row-level: its inner aggregate runs over
        # the window frame, not the GROUP BY
        return False
    if isinstance(e, ast.Func) and e.name in _AGG_FUNCS:
        return True
    return any(_contains_agg(c) for c in _children(e))


def _contains_window(e: Optional[ast.Expr]) -> bool:
    if e is None:
        return False
    if isinstance(e, ast.Window):
        return True
    return any(_contains_window(c) for c in _children(e))


def _children(e: ast.Expr) -> List[ast.Expr]:
    out: List[ast.Expr] = []
    if isinstance(e, ast.Window):
        return (
            list(e.partition_by)
            + [o.expr for o in e.order_by]
            + [a for a in e.func.args if not isinstance(a, ast.Star)]
        )
    if isinstance(e, ast.Unary):
        out = [e.operand]
    elif isinstance(e, ast.Binary):
        out = [e.left, e.right]
    elif isinstance(e, ast.Func):
        out = [a for a in e.args if not isinstance(a, ast.Star)]
    elif isinstance(e, ast.Case):
        out = [x for pair in e.whens for x in pair]
        if e.operand is not None:
            out.append(e.operand)
        if e.default is not None:
            out.append(e.default)
    elif isinstance(e, ast.Cast):
        out = [e.operand]
    elif isinstance(e, (ast.IsNull, ast.Like, ast.InList)):
        out = [e.operand]
        if isinstance(e, ast.Like):
            out.append(e.pattern)
    elif isinstance(e, ast.Between):
        out = [e.operand, e.low, e.high]
    elif isinstance(e, ast.InSubquery):
        # the subquery body is its OWN scope — only the operand belongs
        # to this one (ScalarSubquery/Exists contribute nothing)
        out = [e.operand]
    return out


_WINDOW_ONLY_FUNCS = {
    "row_number", "rank", "dense_rank", "lag", "lead",
    "ntile", "percent_rank", "cume_dist", "nth_value",
}

# aggregates that honor an explicit frame clause (ranking and lag/lead
# are frame-independent by the standard; the frame is ignored for them)
_FRAME_AGGS = {
    "count", "sum", "avg", "mean", "min", "max",
    "first", "first_value", "last", "last_value", "nth_value",
}

_NOT_LITERAL = object()


def _literal_value(e: ast.Expr) -> Any:
    """The python value of a (possibly sign-negated) literal expression."""
    if isinstance(e, ast.Lit):
        return e.value
    if (
        isinstance(e, ast.Unary)
        and e.op == "-"
        and isinstance(e.operand, ast.Lit)
        and isinstance(e.operand.value, (int, float))
        and not isinstance(e.operand.value, bool)
    ):
        return -e.operand.value
    return _NOT_LITERAL


def _eval_window(ev: "_Evaluator", e: ast.Window) -> _TS:
    """Window functions over the evaluator's scope rows.

    Semantics match the reference's DuckDB/SparkSQL backends
    (``/root/reference/fugue_duckdb/execution_engine.py:37``): ranking
    functions need ORDER BY; aggregates-without-ORDER BY see the whole
    partition; aggregates-with-ORDER BY use the SQL default frame (RANGE
    UNBOUNDED PRECEDING .. CURRENT ROW), so peers — rows tying on every
    ORDER BY key — share one value."""
    name = e.func.name
    if name not in _WINDOW_ONLY_FUNCS and name not in _AGG_FUNCS:
        raise SQLExecutionError(f"unsupported window function {name}")
    if e.func.distinct:
        raise SQLExecutionError("DISTINCT is not supported in windows")
    if name in (
        "row_number", "rank", "dense_rank", "percent_rank", "cume_dist"
    ) and e.func.args:
        raise SQLExecutionError(f"{name}() takes no arguments")
    idx = ev.index
    if not idx.is_unique:  # pragma: no cover - scopes use fresh indexes
        raise SQLExecutionError("window over non-unique row index")
    # several items commonly share one OVER clause: memoize the sorted
    # order / partition / peer machinery per (partition_by, order_by)
    # on the evaluator (review finding)
    wcache = getattr(ev, "_window_clause_cache", None)
    if wcache is None:
        wcache = ev._window_clause_cache = {}  # type: ignore[attr-defined]
    ckey = (tuple(e.partition_by), tuple(e.order_by))
    if ckey in wcache:
        order, same_part, part_id, is_peer, peer_id = wcache[ckey]
    else:
        work = pd.DataFrame(index=idx)
        pcols: List[str] = []
        for j, p in enumerate(e.partition_by):
            work[f"p{j}"] = ev.eval(p).series
            pcols.append(f"p{j}")
        # partition keys lead the sort: the shift-based partition/peer
        # detection below requires each partition to be CONTIGUOUS
        ocols: List[str] = []
        sort_cols: List[str] = list(pcols)
        sort_asc: List[bool] = [True] * len(pcols)
        for j, o in enumerate(e.order_by):
            c = f"s{j}"
            work[c] = ev.eval(o.expr).series
            ocols.append(c)
            nulls_first = (
                (o.nulls == "FIRST") if o.nulls is not None else False
            )
            work[f"n_{c}"] = (
                (~work[c].isna()) if nulls_first else work[c].isna()
            )
            sort_cols.extend([f"n_{c}", c])
            sort_asc.extend([True, o.asc])
        if sort_cols:
            order = work.sort_values(
                sort_cols, ascending=sort_asc, kind="stable"
            ).index
        else:
            order = idx
        sw0 = work.loc[order]

        def _same_as_prev(col: str) -> pd.Series:
            s = sw0[col]
            prev = s.shift()
            return (s == prev).fillna(False) | (s.isna() & prev.isna())

        if len(sw0) > 0:
            same_part = pd.Series(True, index=sw0.index)
            for c in pcols:
                same_part &= _same_as_prev(c)
            same_part.iloc[0] = False
            part_id = (~same_part).cumsum()
            same_order = pd.Series(True, index=sw0.index)
            for c in ocols:
                same_order &= _same_as_prev(c)
            is_peer = same_part & same_order
            peer_id = (~is_peer).cumsum()
        else:
            same_part = part_id = is_peer = peer_id = pd.Series(
                [], dtype="int64"
            )
        wcache[ckey] = (order, same_part, part_id, is_peer, peer_id)

    n = len(order)
    if n == 0:
        # empty input: keep the same output TYPE a non-empty input gives
        if name in ("row_number", "rank", "dense_rank", "count", "ntile"):
            tp0: Optional[pa.DataType] = pa.int64()
        elif name in ("avg", "mean", "percent_rank", "cume_dist"):
            tp0 = pa.float64()
        else:
            args0 = e.func.args
            if len(args0) >= 1 and not isinstance(args0[0], ast.Star):
                atp = ev.eval(args0[0]).dtype
            else:
                atp = pa.int64()
            if name == "sum":
                tp0 = (
                    pa.int64()
                    if atp is not None and pa.types.is_integer(atp)
                    else pa.float64()
                )
            else:  # min/max/lag/lead/first/last: the argument's type
                tp0 = atp
        return _TS(pd.Series([], index=idx, dtype=object), tp0)
    grp = part_id.groupby(part_id)
    rn = grp.cumcount() + 1

    def _back(s: pd.Series, tp: Optional[pa.DataType]) -> _TS:
        return _TS(s.reindex(idx), tp)

    if name == "row_number":
        if not e.order_by:
            raise SQLExecutionError("row_number() requires ORDER BY")
        return _back(rn.astype("int64"), pa.int64())
    if name in ("rank", "dense_rank"):
        if not e.order_by:
            raise SQLExecutionError(f"{name}() requires ORDER BY")
        if name == "rank":
            r = rn.where(~is_peer).groupby(part_id).ffill()
        else:
            r = (~is_peer).astype("int64").groupby(part_id).cumsum()
        return _back(r.astype("int64"), pa.int64())
    if name in ("ntile", "percent_rank", "cume_dist"):
        if not e.order_by:
            raise SQLExecutionError(f"{name}() requires ORDER BY")
        psize = grp.transform("size")
        if name == "ntile":
            if len(e.func.args) != 1:
                raise SQLExecutionError("ntile takes one int argument")
            buckets = _literal_value(e.func.args[0])
            if not isinstance(buckets, int) or isinstance(buckets, bool) \
                    or buckets < 1:
                raise SQLExecutionError(
                    "ntile argument must be a positive int literal"
                )
            # first (psize % n) buckets get one extra row (standard SQL)
            q_, rem = psize // buckets, psize % buckets
            cutoff = rem * (q_ + 1)
            in_head = rn <= cutoff
            head = (rn - 1) // (q_ + 1).clip(lower=1) + 1
            tail = rem + (rn - 1 - cutoff) // q_.clip(lower=1) + 1
            r = head.where(in_head, tail)
            return _back(r.astype("int64"), pa.int64())
        if name == "percent_rank":
            srank = rn.where(~is_peer).groupby(part_id).ffill()
            denom = (psize - 1).clip(lower=1)
            r = (srank - 1) / denom
            r = r.where(psize > 1, 0.0)
        else:  # cume_dist: rows <= current row's peer group, over psize
            last_rn = rn.groupby(peer_id).transform("max")
            r = last_rn / psize
        return _back(r.astype("float64"), pa.float64())
    if name in ("lag", "lead"):
        if len(e.func.args) < 1 or len(e.func.args) > 3 or isinstance(
            e.func.args[0], ast.Star
        ):
            raise SQLExecutionError(f"{name} takes (expr[, offset[, default]])")
        offset = 1
        default: Any = None
        if len(e.func.args) >= 2:
            ov = _literal_value(e.func.args[1])
            if not isinstance(ov, int) or isinstance(ov, bool):
                raise SQLExecutionError(f"{name} offset must be an int literal")
            offset = ov
        if len(e.func.args) == 3:
            default = _literal_value(e.func.args[2])
            if default is _NOT_LITERAL:
                raise SQLExecutionError(f"{name} default must be a literal")
        if offset < 0:
            raise SQLExecutionError(f"{name} offset must be >= 0")
        vts = ev.eval(e.func.args[0])
        vs = vts.series.loc[order]
        shifted = vs.groupby(part_id).shift(offset if name == "lag" else -offset)
        if default is not None:
            # the default fills only OUT-OF-PARTITION positions; a shifted-in
            # NULL source value stays NULL (review finding)
            if name == "lag":
                oob = rn <= offset
            else:
                psize = grp.transform("size")
                oob = rn > psize - offset
            shifted = shifted.where(~oob, default)
        tp = vts.dtype
        if (
            default is not None
            and isinstance(default, float)
            and tp is not None
            and pa.types.is_integer(tp)
        ):
            tp = pa.float64()  # a float fill widens an int column
        return _back(shifted, tp)

    if (name == "nth_value" or e.frame is not None) and name in _FRAME_AGGS:
        return _eval_frame_window(ev, e, name, order, part_id, peer_id, _back)

    # aggregates over the window
    star = len(e.func.args) == 1 and isinstance(e.func.args[0], ast.Star)
    if star:
        if name != "count":
            raise SQLExecutionError(f"{name}(*) is not valid")
        vs = pd.Series(1, index=order)
        vts_tp: Optional[pa.DataType] = pa.int64()
    else:
        if len(e.func.args) != 1:
            raise SQLExecutionError(f"window {name} takes one argument")
        vts = ev.eval(e.func.args[0])
        vs = vts.series.loc[order]
        vts_tp = vts.dtype
    sum_tp = (
        pa.int64()
        if vts_tp is not None and pa.types.is_integer(vts_tp)
        else pa.float64()
    )

    def _positional_pick(group_id: pd.Series, first: bool) -> pd.Series:
        """POSITIONAL first/last value per group — unlike pandas
        transform('first'/'last'), a NULL boundary row yields NULL
        (review finding; matches _agg_result's iloc semantics)."""
        new_group = group_id != group_id.shift()
        marker = new_group if first else new_group.shift(-1, fill_value=True)
        mapping = pd.Series(
            vs[marker].values, index=group_id[marker].values
        )
        return group_id.map(mapping)

    if not e.order_by:
        g = vs.groupby(part_id)
        if name == "count":
            r = (
                g.transform("size")
                if star
                else vs.notna().groupby(part_id).transform("sum")
            )
            return _back(r.astype("int64"), pa.int64())
        if name in ("sum", "avg", "mean"):
            cnt = vs.notna().groupby(part_id).transform("sum")
            tot = vs.fillna(0).groupby(part_id).transform("sum")
            if name == "sum":
                return _back(tot.where(cnt > 0), sum_tp)
            return _back(
                (tot / cnt).where(cnt > 0), pa.float64()
            )
        if name in ("min", "max"):
            r = g.transform(name)
            return _back(r, vts_tp)
        if name in ("first", "first_value", "last", "last_value"):
            r = _positional_pick(part_id, first=name.startswith("first"))
            return _back(r, vts_tp)
        raise SQLExecutionError(f"unsupported window aggregate {name}")
    # running (default-frame) aggregates; peers share the group's last value
    cnt = (
        grp.cumcount() + 1
        if star
        else vs.notna().astype("int64").groupby(part_id).cumsum()
    )
    if name == "count":
        r = cnt
    elif name in ("sum", "avg", "mean"):
        tot = vs.fillna(0).groupby(part_id).cumsum()
        r = tot.where(cnt > 0) if name == "sum" else (tot / cnt).where(cnt > 0)
    elif name in ("min", "max"):
        if vs.dtype.kind in "biufcmM":
            r = getattr(vs.groupby(part_id), f"cum{name}")()
            # cummin/cummax leave NaN AT null positions; SQL's
            # null-ignoring frame carries the prior extremum forward
            # (review finding)
            r = r.groupby(part_id).ffill()
        else:
            # strings/objects: pandas cummin rejects them — accumulate
            # per group (review finding)
            pick = min if name == "min" else max

            def _acc(s: pd.Series) -> pd.Series:
                best: Any = None
                out: List[Any] = []
                for v in s:
                    if not pd.isna(v):
                        best = v if best is None else pick(best, v)
                    out.append(best)
                return pd.Series(out, index=s.index, dtype=object)

            r = vs.groupby(part_id, group_keys=False).apply(_acc)
    elif name in ("first", "first_value"):
        r = _positional_pick(part_id, first=True)
    elif name in ("last", "last_value"):
        # frame ends at the current row's peer group: its last row's value
        r = _positional_pick(peer_id, first=False)
    else:
        raise SQLExecutionError(f"unsupported running window {name}")
    r = r.groupby(peer_id).transform("last")
    tp = (
        pa.int64()
        if name == "count"
        else (
            sum_tp
            if name == "sum"
            else (pa.float64() if name in ("avg", "mean") else vts_tp)
        )
    )
    return _back(r, tp)


def _frame_bound_check(b: Tuple[str, Any], unit: str) -> Tuple[str, Any]:
    kind, nv = b
    if kind in ("p", "f"):
        if unit in ("rows", "groups"):
            if not isinstance(nv, int) or isinstance(nv, bool) or nv < 0:
                raise SQLExecutionError(
                    f"{unit.upper()} frame offsets must be "
                    "non-negative integers"
                )
        else:
            if isinstance(nv, bool) or not isinstance(nv, (int, float)) \
                    or nv < 0:
                raise SQLExecutionError(
                    "RANGE frame offsets must be non-negative numbers"
                )
    return kind, nv


def _range_minmax(
    codes: np.ndarray, lo: np.ndarray, hi: np.ndarray, is_min: bool
) -> np.ndarray:
    """Vectorized range-min/max queries over ``codes`` via a sparse
    table: O(n log n) build, O(1) per query. ``lo``/``hi`` are inclusive
    and must satisfy ``0 <= lo <= hi < n`` (callers mask empty frames
    afterwards)."""
    n = len(codes)
    op = np.minimum if is_min else np.maximum
    st = [codes]
    w = 1
    while 2 * w <= n:
        prev = st[-1]
        m = n - 2 * w + 1
        st.append(op(prev[:m], prev[w:w + m]))
        w *= 2
    length = hi - lo + 1
    k = np.floor(np.log2(np.maximum(length, 1))).astype(np.int64)
    out = np.empty(len(lo), dtype=codes.dtype)
    for kk in range(len(st)):
        m = k == kk
        if not m.any():
            continue
        w = 1 << kk
        out[m] = op(st[kk][lo[m]], st[kk][hi[m] - w + 1])
    return out


def _eval_frame_window(
    ev: "_Evaluator",
    e: ast.Window,
    name: str,
    order: pd.Index,
    part_id: pd.Series,
    peer_id: pd.Series,
    _back: Callable[[pd.Series, Optional[pa.DataType]], _TS],
) -> _TS:
    """Aggregates (and first/last/nth_value) over an EXPLICIT frame
    clause — ROWS / RANGE / GROUPS, BETWEEN any pair of bounds — plus
    ``nth_value`` under the default frame. Semantics follow the
    standard as the reference's DuckDB backend executes it
    (``/root/reference/fugue_duckdb/execution_engine.py:37``):
    positional bounds clip to the partition, empty frames yield NULL
    (COUNT 0), RANGE offsets need exactly one numeric ORDER BY key and
    resolve to the null peer group on null keys."""
    frame = e.frame
    if frame is None:  # nth_value under the default frame
        if e.order_by:
            frame = ast.Frame("range", ("up", None), ("c", None))
        else:
            frame = ast.Frame("rows", ("up", None), ("uf", None))
    unit = frame.unit
    if unit == "groups" and not e.order_by:
        raise SQLExecutionError("GROUPS frames require ORDER BY")
    skind, sn = _frame_bound_check(frame.start, unit)
    ekind, en = _frame_bound_check(frame.end, unit)

    n = len(order)
    pos = np.arange(n, dtype=np.int64)
    pid = part_id.to_numpy()
    new_part = np.empty(n, dtype=bool)
    new_part[0] = True
    new_part[1:] = pid[1:] != pid[:-1]
    p_starts = np.flatnonzero(new_part)
    p_ends = np.append(p_starts[1:], n) - 1
    pidx = np.cumsum(new_part) - 1
    part_start = p_starts[pidx]
    part_end = p_ends[pidx]
    gid = peer_id.to_numpy()
    new_peer = np.empty(n, dtype=bool)
    new_peer[0] = True
    new_peer[1:] = gid[1:] != gid[:-1]
    g_starts = np.flatnonzero(new_peer)
    g_ends = np.append(g_starts[1:], n) - 1
    g_glob = np.cumsum(new_peer) - 1
    peer_start = g_starts[g_glob]
    peer_end = g_ends[g_glob]

    # ---- the argument ----------------------------------------------------
    star = len(e.func.args) >= 1 and isinstance(e.func.args[0], ast.Star)
    nth = 0
    if name == "nth_value":
        if len(e.func.args) != 2 or star:
            raise SQLExecutionError("nth_value takes (expr, n)")
        nv = _literal_value(e.func.args[1])
        if not isinstance(nv, int) or isinstance(nv, bool) or nv < 1:
            raise SQLExecutionError(
                "nth_value position must be a positive int literal"
            )
        nth = nv
    elif star:
        if name != "count" or len(e.func.args) != 1:
            raise SQLExecutionError(f"{name}(*) is not valid")
    elif len(e.func.args) != 1:
        raise SQLExecutionError(f"window {name} takes one argument")
    if star:
        vs = pd.Series(1, index=order)
        vts_tp: Optional[pa.DataType] = pa.int64()
    else:
        vts = ev.eval(e.func.args[0])
        vs = vts.series.loc[order]
        vts_tp = vts.dtype

    # ---- frame bounds as positions ---------------------------------------
    def _rows_bound(kind: str, nv: Any, is_start: bool) -> np.ndarray:
        if kind == "up":
            return part_start.copy()
        if kind == "uf":
            return part_end.copy()
        if kind == "c":
            return pos.copy()
        off = nv if kind == "f" else -nv
        return pos + off

    def _groups_bound(kind: str, nv: Any, is_start: bool) -> np.ndarray:
        if kind == "up":
            return part_start.copy()
        if kind == "uf":
            return part_end.copy()
        if kind == "c":
            return peer_start.copy() if is_start else peer_end.copy()
        g_first = g_glob[part_start]
        g_last = g_glob[part_end]
        tg = g_glob + (nv if kind == "f" else -nv)
        if is_start:
            # before the partition's first group -> clamp to it; past the
            # last group -> empty (one past partition end)
            out = g_starts[np.clip(tg, g_first, g_last)]
            return np.where(tg > g_last, part_end + 1, out)
        out = g_ends[np.clip(tg, g_first, g_last)]
        return np.where(tg < g_first, part_start - 1, out)

    _rk: Dict[str, Any] = {}

    def _range_key_state() -> Dict[str, Any]:
        """Order-key machinery for RANGE offsets — computed once and
        shared by the lo and hi bounds (the key expression can be
        arbitrarily expensive)."""
        if _rk:
            return _rk
        if len(e.order_by) != 1:
            raise SQLExecutionError(
                "RANGE frames with offsets require exactly one "
                "ORDER BY expression"
            )
        o = e.order_by[0]
        ks = ev.eval(o.expr).series.loc[order]
        if not pd.api.types.is_numeric_dtype(
            ks.dtype
        ) and not ks.map(
            lambda v: v is None or isinstance(v, (int, float))
        ).all():
            raise SQLExecutionError(
                "RANGE frame offsets require a numeric ORDER BY key"
            )
        kv = pd.to_numeric(ks).astype("float64").to_numpy()
        isna = np.isnan(kv)
        if not o.asc:
            kv = -kv
        nulls_first = (o.nulls == "FIRST") if o.nulls is not None else False
        spans = []  # (part first, part last, non-null first, non-null last)
        for t in range(len(p_starts)):
            s_, e_ = p_starts[t], p_ends[t]
            nn = int(isna[s_:e_ + 1].sum())
            a, b = (s_ + nn, e_) if nulls_first else (s_, e_ - nn)
            spans.append((s_, e_, a, b))
        _rk.update(kv=kv, isna=isna, spans=spans)
        return _rk

    def _range_bound(kind: str, nv: Any, is_start: bool) -> np.ndarray:
        if kind == "up":
            return part_start.copy()
        if kind == "uf":
            return part_end.copy()
        if kind == "c":
            return peer_start.copy() if is_start else peer_end.copy()
        st = _range_key_state()
        kv, isna = st["kv"], st["isna"]
        delta = float(nv) if kind == "f" else -float(nv)
        out = np.empty(n, dtype=np.int64)
        for s_, e_, a, b in st["spans"]:
            if a > b:  # all-null partition
                continue
            seg = kv[a:b + 1]
            tgt = kv[s_:e_ + 1] + delta
            if is_start:
                out[s_:e_ + 1] = a + np.searchsorted(seg, tgt, side="left")
            else:
                out[s_:e_ + 1] = (
                    a + np.searchsorted(seg, tgt, side="right") - 1
                )
        # null keys: the frame bound resolves to the null peer group
        out[isna] = peer_start[isna] if is_start else peer_end[isna]
        return out

    bound = {"rows": _rows_bound, "groups": _groups_bound,
             "range": _range_bound}[unit]
    lo = bound(skind, sn, True)
    hi = bound(ekind, en, False)
    lo = np.maximum(lo, part_start)
    hi = np.minimum(hi, part_end)
    empty = lo > hi
    lo_s = np.clip(lo, 0, n - 1)
    hi_s = np.clip(hi, 0, n - 1)

    # ---- aggregate over [lo, hi] -----------------------------------------
    def _ser(arr: np.ndarray) -> pd.Series:
        return pd.Series(arr, index=order)

    if name == "count":
        if star:
            r = _ser(np.where(empty, 0, hi - lo + 1))
        else:
            c = np.concatenate(
                [[0], np.cumsum(vs.notna().to_numpy(dtype="int64"))]
            )
            r = _ser(np.where(empty, 0, c[hi_s + 1] - c[lo_s]))
        return _back(r.astype("int64"), pa.int64())
    if name in ("sum", "avg", "mean"):
        fv = vs.fillna(0).to_numpy(dtype="float64")
        cs = np.concatenate([[0.0], np.cumsum(fv)])
        cn = np.concatenate(
            [[0], np.cumsum(vs.notna().to_numpy(dtype="int64"))]
        )
        cnt = np.where(empty, 0, cn[hi_s + 1] - cn[lo_s])
        tot = np.where(empty, 0.0, cs[hi_s + 1] - cs[lo_s])
        sum_tp = (
            pa.int64()
            if vts_tp is not None and pa.types.is_integer(vts_tp)
            else pa.float64()
        )
        if name == "sum":
            r = _ser(tot).where(cnt > 0)
            if sum_tp == pa.int64():
                # exact for the int64 range a float64 cumsum preserves
                r = r.round()
            return _back(r, sum_tp)
        return _back(
            _ser(np.where(cnt > 0, tot / np.maximum(cnt, 1), np.nan)).where(
                cnt > 0
            ),
            pa.float64(),
        )
    if name in ("min", "max"):
        codes, uniques = pd.factorize(vs, sort=True)
        cf = codes.astype(np.float64)
        cf[codes < 0] = np.inf if name == "min" else -np.inf
        res = _range_minmax(cf, lo_s, hi_s, name == "min")
        ok = np.isfinite(res) & ~empty
        vals = np.empty(n, dtype=object)
        vals[~ok] = None
        if ok.any():
            taken = np.asarray(uniques, dtype=object)[
                res[ok].astype(np.int64)
            ]
            vals[ok] = taken
        return _back(_ser(vals), vts_tp)
    if name in ("first", "first_value", "last", "last_value", "nth_value"):
        if name == "nth_value":
            at = lo + nth - 1
            bad = empty | (at > hi)
        elif name.startswith("first"):
            at = lo
            bad = empty
        else:
            at = hi
            bad = empty
        arr = vs.to_numpy()
        r = _ser(arr[np.clip(at, 0, n - 1)]).where(~_ser(bad))
        return _back(r, vts_tp)
    raise AssertionError(name)  # the _FRAME_AGGS gate owns the contract


def _collect_aggs(e: ast.Expr, out: List[ast.Func]) -> None:
    if isinstance(e, ast.Func) and e.name in _AGG_FUNCS:
        if e not in out:
            out.append(e)
        return
    for c in _children(e):
        _collect_aggs(c, out)


def _agg_result(
    grouped: Any, func: ast.Func, label: str, arg_type: Optional[pa.DataType]
) -> Tuple[pd.Series, Optional[pa.DataType]]:
    name = func.name
    if name == "count":
        if func.distinct:
            return grouped[label].nunique(dropna=True), pa.int64()
        if len(func.args) == 1 and isinstance(func.args[0], ast.Star):
            return grouped[label].size(), pa.int64()
        return grouped[label].count(), pa.int64()
    if name in ("avg", "mean"):
        if func.distinct:
            return (
                grouped[label].agg(lambda s: s.drop_duplicates().mean()),
                pa.float64(),
            )
        return grouped[label].mean(), pa.float64()
    if name == "sum":
        col = grouped[label]
        if func.distinct:
            res = col.agg(lambda s: s.dropna().drop_duplicates().sum()
                          if s.notna().any() else None)
        else:
            res = col.sum(min_count=1)
        tp = pa.int64() if arg_type is not None and \
            pa.types.is_integer(arg_type) else pa.float64()
        return res, tp
    if name == "min":
        return grouped[label].min(), arg_type
    if name == "max":
        return grouped[label].max(), arg_type
    if name in ("first", "first_value"):
        return grouped[label].agg(
            lambda s: s.iloc[0] if len(s) > 0 else None
        ), arg_type
    if name in ("last", "last_value"):
        return grouped[label].agg(
            lambda s: s.iloc[-1] if len(s) > 0 else None
        ), arg_type
    if name in VARIANCE_FUNCS:
        ddof, f2 = variance_ddof(name), variance_stat(name)
        if func.distinct:
            res = grouped[label].agg(
                lambda s: getattr(s.drop_duplicates(), f2)(ddof=ddof)
            )
        else:
            res = getattr(grouped[label], f2)(ddof=ddof)
        return res, pa.float64()
    if name == "median":
        if func.distinct:
            return grouped[label].agg(
                lambda s: s.drop_duplicates().median()
            ), pa.float64()
        return grouped[label].median(), pa.float64()
    raise SQLExecutionError(f"unsupported aggregation {name}")


def _global_agg_result(
    frame: pd.DataFrame, func: ast.Func, label: str,
    arg_type: Optional[pa.DataType],
) -> Tuple[Any, Optional[pa.DataType]]:
    s = frame[label]
    name = func.name
    if name == "count":
        if func.distinct:
            return s.nunique(dropna=True), pa.int64()
        if len(func.args) == 1 and isinstance(func.args[0], ast.Star):
            return len(s), pa.int64()
        return s.count(), pa.int64()
    if name in ("avg", "mean"):
        vals = s.drop_duplicates() if func.distinct else s
        return (vals.mean() if len(vals) else None), pa.float64()
    if name == "sum":
        vals = s.dropna().drop_duplicates() if func.distinct else s
        res = vals.sum(min_count=1) if len(vals) else None
        tp = pa.int64() if arg_type is not None and \
            pa.types.is_integer(arg_type) else pa.float64()
        return (None if res is None or pd.isna(res) else res), tp
    if name == "min":
        return (s.min() if s.notna().any() else None), arg_type
    if name == "max":
        return (s.max() if s.notna().any() else None), arg_type
    if name in ("first", "first_value"):
        return (s.iloc[0] if len(s) > 0 else None), arg_type
    if name in ("last", "last_value"):
        return (s.iloc[-1] if len(s) > 0 else None), arg_type
    if name in VARIANCE_FUNCS:
        vals = s.drop_duplicates() if func.distinct else s
        return (
            getattr(vals, variance_stat(name))(ddof=variance_ddof(name))
            if len(vals)
            else None
        ), pa.float64()
    if name == "median":
        vals = s.drop_duplicates() if func.distinct else s
        return (vals.median() if len(vals) else None), pa.float64()
    raise SQLExecutionError(f"unsupported aggregation {name}")


# ---- SELECT execution ---------------------------------------------------


def _run_select(q: ast.Select, env: Dict[str, _Table]) -> _Table:
    if q.from_ is None:
        scope = _Scope(pd.DataFrame({"_": [0]})[[]], [])
        scope.frame.index = pd.RangeIndex(1)
    else:
        scope = _build_scope(q.from_, env)
    if q.where is not None:
        if _contains_agg(q.where):
            raise SQLExecutionError("WHERE cannot contain aggregations")
        if _contains_window(q.where):
            raise SQLExecutionError("WHERE cannot contain window functions")
        mask = _to_bool_mask(
            _Evaluator(scope, env=env).eval(q.where).series
        )
        scope = _Scope(scope.frame[mask], scope.entries)

    has_agg = (
        len(q.group_by) > 0
        or any(
            not isinstance(it.expr, ast.Star) and _contains_agg(it.expr)
            for it in q.items
        )
        or (q.having is not None)
    )
    if has_agg and (
        _contains_window(q.having)
        or any(_contains_window(g) for g in q.group_by)
        or any(
            not isinstance(it.expr, ast.Star) and _contains_window(it.expr)
            for it in q.items
        )
    ):
        raise SQLExecutionError(
            "window functions over aggregated output are not supported"
        )
    resolver: Optional[Callable[[ast.Expr], _TS]]
    if has_agg:
        out, resolver = _run_agg_select(q, scope, env)
    else:
        out = _run_plain_select(q, scope, env)
        ev = _Evaluator(scope, env=env)
        resolver = ev.eval
    if q.distinct:
        # keep the original index so order keys can still be reindexed
        out = _Table(out.frame.drop_duplicates(), out.names, out.types)
    out = _apply_order_limit(out, q.order_by, q.limit, q.offset, resolver)
    return out


def _output_name(item: ast.SelectItem, i: int) -> str:
    if item.alias is not None:
        return item.alias
    if isinstance(item.expr, ast.Col):
        return item.expr.name
    return f"col_{i}"


def _run_plain_select(
    q: ast.Select, scope: _Scope, env: Optional[Dict[str, _Table]] = None
) -> _Table:
    ev = _Evaluator(scope, env=env)
    cols: List[Tuple[str, _TS]] = []
    for i, item in enumerate(q.items):
        if isinstance(item.expr, ast.Star):
            for e in scope.star_entries(item.expr.table):
                cols.append((e.name, _TS(scope.frame[e.label], e.dtype)))
        else:
            cols.append((_output_name(item, i), ev.eval(item.expr)))
    names = [c[0] for c in cols]
    _check_dup(names)
    frame = pd.DataFrame(
        {f"o{i}": ts.series for i, (_, ts) in enumerate(cols)},
        index=scope.frame.index,
    )
    if len(cols) > 0 and len(scope.frame.index) == 0:
        frame = frame.iloc[0:0]
    return _Table(frame, names, [ts.dtype for _, ts in cols])


def _check_dup(names: List[str]) -> None:
    seen = set()
    for n in names:
        if n in seen:
            raise SQLExecutionError(f"duplicated output column {n}")
        seen.add(n)


class _AggContext:
    """Post-aggregation scope: group keys + aggregated values by node."""

    def __init__(self, env: Optional[Dict[str, _Table]] = None) -> None:
        self.key_exprs: List[ast.Expr] = []
        self.key_labels: List[str] = []
        self.key_types: List[Optional[pa.DataType]] = []
        self.agg_nodes: List[ast.Func] = []
        self.agg_labels: List[str] = []
        self.agg_types: List[Optional[pa.DataType]] = []
        self.frame = pd.DataFrame()
        self.env = env

    def eval_post(self, e: ast.Expr, scope: _Scope) -> _TS:
        """Evaluate over the aggregated frame, mapping group-by exprs and
        agg funcs to their computed columns."""
        for k, lbl, tp in zip(self.key_exprs, self.key_labels, self.key_types):
            if e == k:
                return _TS(self.frame[lbl], tp)
            if isinstance(e, ast.Col) and isinstance(k, ast.Col) \
                    and e.name == k.name and e.table is None:
                return _TS(self.frame[lbl], tp)
        if isinstance(e, ast.Func) and e.name in _AGG_FUNCS:
            for node, lbl, tp in zip(
                self.agg_nodes, self.agg_labels, self.agg_types
            ):
                if e == node:
                    return _TS(self.frame[lbl], tp)
            raise SQLExecutionError(f"aggregation {e} was not computed")
        if isinstance(e, ast.Col):
            raise SQLExecutionError(
                f"column {_qname(e.name, e.table)} is not in GROUP BY"
            )
        # structural recursion via a shadow evaluator over the agg frame.
        # Plain-column group keys become scope entries (qualified with
        # their PRE-aggregation qualifier) so qualified refs — notably
        # correlated subqueries' outer references like ``a.k`` in HAVING
        # — resolve to the grouped key columns (review finding)
        entries: List[_Entry] = []
        for k, lbl, tp in zip(
            self.key_exprs, self.key_labels, self.key_types
        ):
            if isinstance(k, ast.Col):
                try:
                    src = scope.resolve(k.name, k.table)
                except SQLExecutionError:
                    continue
                entries.append(_Entry(src.qual, src.name, lbl, tp))
        sub = _Evaluator(_Scope(self.frame, entries), env=self.env)
        return _eval_with_hook(sub, e, lambda x: self._hook(x, scope))

    def _hook(self, e: ast.Expr, scope: _Scope) -> Optional[_TS]:
        for k, lbl, tp in zip(self.key_exprs, self.key_labels, self.key_types):
            if e == k or (
                isinstance(e, ast.Col) and isinstance(k, ast.Col)
                and e.name == k.name and e.table is None
            ):
                return _TS(self.frame[lbl], tp)
        if isinstance(e, ast.Func) and e.name in _AGG_FUNCS:
            for node, lbl, tp in zip(
                self.agg_nodes, self.agg_labels, self.agg_types
            ):
                if e == node:
                    return _TS(self.frame[lbl], tp)
        return None


def _eval_with_hook(
    ev: _Evaluator, e: ast.Expr, hook: Callable[[ast.Expr], Optional[_TS]]
) -> _TS:
    hooked = hook(e)
    if hooked is not None:
        return hooked
    orig = ev.eval

    def patched(x: ast.Expr) -> _TS:
        h = hook(x)
        if h is not None:
            return h
        return orig(x)

    ev.eval = patched  # type: ignore[method-assign]
    try:
        return orig(e)
    finally:
        ev.eval = orig  # type: ignore[method-assign]


def _resolve_groupby_expr(
    g: ast.Expr, q: ast.Select, scope: _Scope
) -> ast.Expr:
    """GROUP BY ordinal or select alias resolves to the item's expression.

    A real input column takes precedence over a select alias of the same
    (case-folded) name — Postgres/DuckDB resolution order."""
    if isinstance(g, ast.Lit) and isinstance(g.value, int) \
            and not isinstance(g.value, bool):
        idx = g.value - 1
        if idx < 0 or idx >= len(q.items):
            raise SQLExecutionError(f"GROUP BY ordinal {g.value} out of range")
        return q.items[idx].expr
    if isinstance(g, ast.Col) and g.table is None:
        cands = scope.candidates(g.name, None)
        if len(cands) > 1:
            raise SQLExecutionError(f"ambiguous column: {_qname(g.name, None)}")
        if len(cands) == 1:
            return g  # input column wins over any same-named alias
        for it in q.items:
            if it.alias is not None and it.alias.lower() == g.name.lower():
                return it.expr
    return g


def _run_agg_select(
    q: ast.Select, scope: _Scope, env: Optional[Dict[str, _Table]] = None
) -> Tuple[_Table, Callable[[ast.Expr], _TS]]:
    ctx = _AggContext(env)
    ctx.key_exprs = [_resolve_groupby_expr(g, q, scope) for g in q.group_by]
    for k in ctx.key_exprs:
        if _contains_agg(k):
            raise SQLExecutionError("GROUP BY cannot contain aggregations")
    aggs: List[ast.Func] = []
    for it in q.items:
        if isinstance(it.expr, ast.Star):
            raise SQLExecutionError("SELECT * cannot be used with GROUP BY")
        _collect_aggs(it.expr, aggs)
    if q.having is not None:
        _collect_aggs(q.having, aggs)
    for o in q.order_by:
        _collect_aggs(o.expr, aggs)
    ctx.agg_nodes = aggs

    ev = _Evaluator(scope, env=env)
    work = pd.DataFrame(index=scope.frame.index)
    key_labels = []
    for i, k in enumerate(ctx.key_exprs):
        ts = ev.eval(k)
        lbl = f"k{i}"
        work[lbl] = ts.series
        key_labels.append(lbl)
        ctx.key_labels.append(lbl)
        ctx.key_types.append(ts.dtype)
    arg_types: List[Optional[pa.DataType]] = []
    for i, node in enumerate(aggs):
        lbl = f"a{i}"
        if len(node.args) == 1 and isinstance(node.args[0], ast.Star):
            work[lbl] = 1
            arg_types.append(pa.int64())
        else:
            if len(node.args) != 1:
                raise SQLExecutionError(
                    f"aggregation {node.name} takes one argument"
                )
            ts = ev.eval(node.args[0])
            work[lbl] = ts.series
            arg_types.append(ts.dtype)
        ctx.agg_labels.append(lbl)

    if len(key_labels) == 0:
        data: Dict[str, Any] = {}
        for node, lbl, atp in zip(aggs, ctx.agg_labels, arg_types):
            val, tp = _global_agg_result(work, node, lbl, atp)
            data[lbl] = [val]
            ctx.agg_types.append(tp)
        ctx.frame = pd.DataFrame(data) if data else pd.DataFrame(index=[0])
    else:
        grouped = work.groupby(key_labels, dropna=False, sort=False)
        pieces: Dict[str, pd.Series] = {}
        for node, lbl, atp in zip(aggs, ctx.agg_labels, arg_types):
            res, tp = _agg_result(grouped, node, lbl, atp)
            pieces[lbl] = res
            ctx.agg_types.append(tp)
        if pieces:
            agg_frame = pd.DataFrame(pieces).reset_index()
        else:
            agg_frame = grouped.size().reset_index(name="_sz") \
                .drop(columns=["_sz"])
        ctx.frame = agg_frame

    if q.having is not None:
        mask = _to_bool_mask(ctx.eval_post(q.having, scope).series)
        ctx.frame = ctx.frame[mask]

    cols: List[Tuple[str, _TS]] = []
    for i, it in enumerate(q.items):
        cols.append((_output_name(it, i), ctx.eval_post(it.expr, scope)))
    names = [c[0] for c in cols]
    _check_dup(names)
    frame = pd.DataFrame(
        {f"o{i}": ts.series for i, (_, ts) in enumerate(cols)},
        index=ctx.frame.index,
    )
    out = _Table(frame, names, [ts.dtype for _, ts in cols])
    return out, (lambda e: ctx.eval_post(e, scope))


def _apply_order_limit(
    t: _Table,
    order_by: List[ast.OrderItem],
    limit: Optional[int],
    offset: Optional[int],
    resolver: Optional[Callable[[ast.Expr], _TS]],
) -> _Table:
    if order_by:
        keys = []
        for j, o in enumerate(order_by):
            ts = _order_key(t, o, resolver)
            keys.append((f"s{j}", ts.series, o))
        t = _sort_table(t, keys, t.frame.index)
    t = _Table(t.frame.reset_index(drop=True), t.names, t.types)
    return _apply_limit(t, limit, offset)


def _order_key(
    t: _Table, o: ast.OrderItem,
    resolver: Optional[Callable[[ast.Expr], _TS]],
) -> _TS:
    e = o.expr
    if isinstance(e, ast.Lit) and isinstance(e.value, int) \
            and not isinstance(e.value, bool):
        idx = e.value - 1
        if 0 <= idx < len(t.names):
            return _TS(t.frame.iloc[:, idx], t.types[idx])
    if isinstance(e, ast.Col) and e.table is None:
        if e.name in t.names:
            idx = t.names.index(e.name)
            return _TS(t.frame.iloc[:, idx], t.types[idx])
        # SQL identifiers fold case: ORDER BY k matches output column K
        folded = [n.lower() for n in t.names]
        if folded.count(e.name.lower()) == 1:
            idx = folded.index(e.name.lower())
            return _TS(t.frame.iloc[:, idx], t.types[idx])
    if resolver is not None:
        ts = resolver(e)
        return _TS(ts.series.reindex(t.frame.index), ts.dtype)
    raise SQLExecutionError(f"cannot resolve ORDER BY expression {e}")


def _sort_table(
    t: _Table, keys: List[Tuple[str, pd.Series, ast.OrderItem]],
    index: pd.Index,
) -> _Table:
    sorter = pd.DataFrame(
        {lbl: s.reindex(index) for lbl, s, _ in keys}, index=index
    )
    by = [lbl for lbl, _, _ in keys]
    ascending = [o.asc for _, _, o in keys]
    # pandas supports one na_position for all keys; emulate per-key NULLS
    # FIRST/LAST via a null-rank column per key
    frames = []
    for lbl, _, o in keys:
        nulls_first = (o.nulls == "FIRST") if o.nulls is not None else False
        nf = sorter[lbl].isna()
        frames.append((f"n_{lbl}", (~nf) if nulls_first else nf))
    for lbl, s in frames:
        sorter[lbl] = s
    interleaved = []
    asc2 = []
    for (lbl, _, o), (nlbl, _s) in zip(keys, frames):
        interleaved.extend([nlbl, lbl])
        asc2.extend([True, o.asc])
    del by, ascending
    order = sorter.sort_values(interleaved, ascending=asc2, kind="stable").index
    return _Table(t.frame.loc[order], t.names, t.types)


def _apply_limit(
    t: _Table, limit: Optional[int], offset: Optional[int]
) -> _Table:
    if offset is not None:
        t = _Table(t.frame.iloc[offset:], t.names, t.types)
    if limit is not None:
        t = _Table(t.frame.iloc[:limit], t.names, t.types)
    return _Table(t.frame.reset_index(drop=True), t.names, t.types)


# ---- set operations -----------------------------------------------------


def _unify_types(
    a: Optional[pa.DataType], b: Optional[pa.DataType]
) -> Optional[pa.DataType]:
    if a is None:
        return b
    if b is None or a.equals(b):
        return a
    numeric = (pa.types.is_integer, pa.types.is_floating)
    if any(f(a) for f in numeric) and any(f(b) for f in numeric):
        if pa.types.is_floating(a) or pa.types.is_floating(b):
            return pa.float64()
        return pa.int64()
    return pa.string()


def _run_setop(q: ast.SetOp, env: Dict[str, _Table]) -> _Table:
    left = _run(q.left, env)
    right = _run(q.right, env)
    if len(left.names) != len(right.names):
        raise SQLExecutionError(
            f"{q.op} requires equal column counts "
            f"({len(left.names)} vs {len(right.names)})"
        )
    lf = left.frame.copy(deep=False)
    rf = right.frame.copy(deep=False)
    labels = [f"u{i}" for i in range(len(left.names))]
    lf.columns = labels
    rf.columns = labels
    types = [
        _unify_types(a, b) for a, b in zip(left.types, right.types)
    ]
    # coerce BOTH sides to the unified column types up front: dedup and
    # the multiset merges below compare values, and pandas refuses to
    # merge int64 against str outright (review finding)
    for lbl, tp, ltp, rtp in zip(labels, types, left.types, right.types):
        if ltp is None or rtp is None:
            # NULL-literal side: compare in object space — concat handles
            # it natively, but the merge-based ops need matching dtypes
            # (review finding); set-op NULLs compare equal, which pandas'
            # merge factorization gives for None keys
            if str(lf[lbl].dtype) != str(rf[lbl].dtype):
                lf[lbl] = lf[lbl].astype(object)
                rf[lbl] = rf[lbl].astype(object)
            continue
        if str(lf[lbl].dtype) == str(rf[lbl].dtype):
            continue
        if tp is not None and pa.types.is_string(tp):
            for f in (lf, rf):
                s = f[lbl]
                nulls = s.isna()
                o = s.astype(object)
                o[~nulls] = s[~nulls].map(_to_str_scalar)
                o[nulls.to_numpy(dtype=bool)] = None
                f[lbl] = o
        else:
            try:
                dt = tp.to_pandas_dtype() if tp is not None else float
                lf[lbl] = lf[lbl].astype(dt)
                rf[lbl] = rf[lbl].astype(dt)
            except Exception:
                raise SQLExecutionError(
                    f"incompatible column types in {q.op}"
                )
    if q.op == "UNION":
        res = pd.concat([lf, rf], ignore_index=True)
        if not q.all:
            res = res.drop_duplicates().reset_index(drop=True)
    elif q.op in ("EXCEPT", "INTERSECT") and q.all:
        # multiset semantics (standard SQL ... ALL): pair off occurrences
        # — EXCEPT ALL keeps each left row whose occurrence index exceeds
        # the right-side count; INTERSECT ALL keeps those within it
        lo = lf.assign(
            _occ=lf.groupby(labels, dropna=False).cumcount()
        )
        rcnt = (
            rf.groupby(labels, dropna=False)
            .size()
            .rename("_rc")
            .reset_index()
        )
        merged = lo.merge(rcnt, on=labels, how="left")
        rc = merged["_rc"].fillna(0)
        keep = merged["_occ"] >= rc if q.op == "EXCEPT" else (
            merged["_occ"] < rc
        )
        res = merged[keep].drop(columns=["_occ", "_rc"]).reset_index(
            drop=True
        )
    elif q.op == "EXCEPT":
        ld = lf.drop_duplicates()
        rd = rf.drop_duplicates()
        merged = ld.merge(rd, on=labels, how="left", indicator=True)
        res = merged[merged["_merge"] == "left_only"] \
            .drop(columns=["_merge"]).reset_index(drop=True)
    elif q.op == "INTERSECT":
        ld = lf.drop_duplicates()
        rd = rf.drop_duplicates()
        res = ld.merge(rd, on=labels, how="inner").reset_index(drop=True)
    else:
        raise SQLExecutionError(f"unsupported set op {q.op}")
    out = _Table(res, list(left.names), types)
    return _apply_order_limit(out, q.order_by, q.limit, q.offset, None)
