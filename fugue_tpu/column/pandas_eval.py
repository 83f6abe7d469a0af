"""Evaluate the column algebra directly on pandas — the native engine's
compute path for select/filter/assign/aggregate (replaces the reference's
qpd-SQL-on-pandas dependency with a direct expression interpreter; SQL
semantics: Kleene logic via pandas nullable booleans, nulls ignored by aggs).
"""

import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pandas as pd
import pyarrow as pa

from fugue_tpu.column.expressions import (
    ColumnExpr,
    _BinaryOpExpr,
    _FuncExpr,
    _LitColumnExpr,
    _NamedColumnExpr,
    _UnaryOpExpr,
)
from fugue_tpu.column.functions import (
    VARIANCE_FUNCS,
    is_agg,
    variance_ddof,
    variance_stat,
)
from fugue_tpu.column.sql import SelectColumns
from fugue_tpu.schema import Schema
from fugue_tpu.utils.assertion import assert_or_throw


def sql_fmod(a: pd.Series, b: pd.Series) -> pd.Series:
    """SQL modulo: truncated (sign of dividend, MOD(-7, 3) = -1), NULL on
    a zero divisor, with numpy's out-of-domain chatter suppressed. Shared
    by every host evaluator so the semantics cannot drift apart."""
    with np.errstate(invalid="ignore", divide="ignore"):
        return np.fmod(a, b).where(b != 0)


def eval_expr(df: pd.DataFrame, expr: ColumnExpr) -> pd.Series:
    """Evaluate a non-aggregation expression to a Series aligned with df."""
    s = _eval(df, expr)
    if expr.as_type is not None:
        s = _cast_series(s, expr.as_type)
    return s


def _bool_series(s: pd.Series) -> pd.Series:
    """To pandas nullable boolean (Kleene logic for &/|)."""
    if s.dtype == "boolean":
        return s
    return s.astype("boolean")


def _eval(df: pd.DataFrame, expr: ColumnExpr) -> pd.Series:
    if isinstance(expr, _NamedColumnExpr):
        assert_or_throw(not expr.wildcard, ValueError("can't evaluate wildcard"))
        return df[expr.name]
    if isinstance(expr, _LitColumnExpr):
        v = expr.value
        return pd.Series([v] * len(df), index=df.index)
    if isinstance(expr, _UnaryOpExpr):
        inner = _eval(df, expr.col)
        if expr.op == "IS_NULL":
            return inner.isna().astype("boolean")
        if expr.op == "NOT_NULL":
            return (~inner.isna()).astype("boolean")
        if expr.op == "-":
            return -inner
        if expr.op == "~":
            return ~_bool_series(inner)
        raise NotImplementedError(f"unary op {expr.op}")
    if isinstance(expr, _BinaryOpExpr):
        left = _eval(df, expr.left)
        right = _eval(df, expr.right)
        op = expr.op
        if op in ("&", "|"):
            lb, rb = _bool_series(left), _bool_series(right)
            return lb & rb if op == "&" else lb | rb
        if op in ("==", "!=", "<", "<=", ">", ">="):
            # SQL: comparison with NULL yields NULL
            nulls = left.isna() | right.isna()
            func = {
                "==": lambda a, b: a == b,
                "!=": lambda a, b: a != b,
                "<": lambda a, b: a < b,
                "<=": lambda a, b: a <= b,
                ">": lambda a, b: a > b,
                ">=": lambda a, b: a >= b,
            }[op]
            with np.errstate(invalid="ignore"):
                res = func(left, right)
            res = res.astype("boolean")
            res[nulls] = pd.NA
            return res
        if op == "+":
            return left + right
        if op == "-":
            return left - right
        if op == "*":
            return left * right
        if op == "/":
            return left.astype("float64") / right
        raise NotImplementedError(f"binary op {op}")
    if isinstance(expr, _FuncExpr) and not expr.is_aggregation:
        f = expr.func.lower()
        if f == "coalesce":
            args = [_eval(df, a) for a in expr.args]
            res = args[0]
            for a in args[1:]:
                res = res.combine_first(a)
            return res
        if f == "like":
            operand = _eval(df, expr.args[0])
            pattern = expr.args[1]
            negated = expr.args[2]
            assert_or_throw(
                isinstance(negated, _LitColumnExpr),
                ValueError("LIKE negation must be a literal"),
            )
            if isinstance(pattern, _LitColumnExpr) and isinstance(
                pattern.value, str
            ):
                rx = compile_like_regex(pattern.value)
                res = operand.astype("string").str.fullmatch(rx).astype(
                    "boolean"
                )
                if negated.value:
                    res = ~res
                res[operand.isna()] = pd.NA  # NULL LIKE anything -> NULL
                return res
            # dynamic pattern: compile per DISTINCT pattern value;
            # NULL on either side -> NULL
            p = _eval(df, pattern)
            cache: Dict[Any, Any] = {}
            vals: List[Any] = []
            for v, pv in zip(operand, p):
                if pd.isna(v) or pd.isna(pv):
                    vals.append(None)
                    continue
                crx = cache.get(pv)
                if crx is None:
                    crx = compile_like_regex(str(pv))
                    cache[pv] = crx
                vals.append(crx.fullmatch(str(v)) is not None)
            res = pd.Series(vals, index=df.index, dtype=object).astype(
                "boolean"
            )
            return ~res if negated.value else res
        if f == "case_when":
            # cond/value pairs + default; NULL conditions don't match —
            # fill NA up front so one NULL condition can't poison the
            # matched accumulator for later branches (review finding)
            default = _eval(df, expr.args[-1])
            res = default.copy()
            matched = pd.Series(False, index=df.index)
            for i in range(0, len(expr.args) - 1, 2):
                cond = (
                    _bool_series(_eval(df, expr.args[i]))
                    .fillna(False)
                    .astype(bool)
                )
                val = _eval(df, expr.args[i + 1])
                take = cond & ~matched
                if take.any():
                    res = val.where(take, res)
                matched = matched | cond
            return res
        if f in _NUM_UNARY:
            s = pd.to_numeric(_eval(df, expr.args[0]), errors="coerce")
            # out-of-domain inputs (SQRT(-4), LN(0)) yield NaN by SQL
            # intent, not as a numpy anomaly — keep -W error runs clean
            with np.errstate(invalid="ignore", divide="ignore"):
                res = _NUM_UNARY[f](s)
            return pd.Series(res, index=df.index)
        if f == "round":
            s = pd.to_numeric(_eval(df, expr.args[0]), errors="coerce")
            digits = _scalar_arg(df, expr.args, 1, 0)
            return s.round(int(digits))
        if f in ("power", "pow"):
            a = pd.to_numeric(_eval(df, expr.args[0]), errors="coerce")
            b = pd.to_numeric(_eval(df, expr.args[1]), errors="coerce")
            return a**b
        if f == "mod":
            a = pd.to_numeric(_eval(df, expr.args[0]), errors="coerce")
            b = pd.to_numeric(_eval(df, expr.args[1]), errors="coerce")
            return sql_fmod(a, b)
        if f == "nullif":
            a = _eval(df, expr.args[0])
            b = _eval(df, expr.args[1])
            eq = pd.Series(False, index=df.index)
            with np.errstate(invalid="ignore"):
                eq = (a == b) & a.notna() & b.notna()
            return a.astype(object).where(~eq, None)
        if f in ("if", "iif"):
            cond = _bool_series(_eval(df, expr.args[0])).fillna(False)
            yes = _eval(df, expr.args[1])
            no = _eval(df, expr.args[2])
            return yes.astype(object).where(
                cond.astype(bool), no.astype(object)
            )
        if f in _STR_UNARY:
            s = _eval(df, expr.args[0])
            nulls = s.isna()
            res = _STR_UNARY[f](s.astype(object).astype(str)).astype(object)
            res[nulls.to_numpy(dtype=bool)] = None
            return res
        if f in ("length", "len"):
            s = _eval(df, expr.args[0])
            res = s.astype(object).astype(str).str.len().astype(object)
            res[s.isna().to_numpy(dtype=bool)] = None
            return res
        if f in ("substring", "substr"):
            s = _eval(df, expr.args[0])
            starts = pd.to_numeric(_eval(df, expr.args[1]), errors="coerce")
            lens = (
                pd.to_numeric(_eval(df, expr.args[2]), errors="coerce")
                if len(expr.args) > 2
                else None
            )
            return sql_substring(s, starts, lens)
        if f == "concat":
            res: Optional[pd.Series] = None
            nulls: Optional[pd.Series] = None
            for a in expr.args:
                s = _eval(df, a)
                nulls = s.isna() if nulls is None else (nulls | s.isna())
                part = s.astype(object).astype(str)
                res = part if res is None else res + part
            assert res is not None and nulls is not None
            res = res.astype(object)
            res[nulls.to_numpy(dtype=bool)] = None
            return res
        if f == "replace":
            s = _eval(df, expr.args[0])
            nulls = s.isna()
            old = str(_scalar_arg(df, expr.args, 1, ""))
            new = str(_scalar_arg(df, expr.args, 2, ""))
            res = s.astype(object).astype(str).str.replace(
                old, new, regex=False
            ).astype(object)
            res[nulls.to_numpy(dtype=bool)] = None
            return res
        raise NotImplementedError(f"function {expr.func} not supported on pandas")
    raise NotImplementedError(f"can't evaluate {expr}")


_NUM_UNARY: Dict[str, Any] = {
    "abs": lambda s: s.abs(),
    "floor": np.floor,
    "ceil": np.ceil,
    "ceiling": np.ceil,
    "sqrt": np.sqrt,
    "exp": np.exp,
    "ln": np.log,
    "log": np.log,
    "log2": np.log2,
    "log10": np.log10,
    "sin": np.sin,
    "cos": np.cos,
    "tan": np.tan,
    "sign": np.sign,
}

_STR_UNARY: Dict[str, Any] = {
    "upper": lambda s: s.str.upper(),
    "ucase": lambda s: s.str.upper(),
    "lower": lambda s: s.str.lower(),
    "lcase": lambda s: s.str.lower(),
    "trim": lambda s: s.str.strip(),
    "ltrim": lambda s: s.str.lstrip(),
    "rtrim": lambda s: s.str.rstrip(),
    "reverse": lambda s: s.str[::-1],
}


def _scalar_arg(df: pd.DataFrame, args: List[Any], i: int, default: Any) -> Any:
    """A scalar parameter (round digits, substring bounds, ...): the
    first value of the evaluated argument — same convention as the SQL
    runner's scalar functions."""
    if i >= len(args):
        return default
    s = _eval(df, args[i])
    return s.iloc[0] if len(s) else default


def sql_substring(
    s: pd.Series,
    starts: pd.Series,
    lens: Optional[pd.Series],
) -> pd.Series:
    """SQL SUBSTRING over object-typed strings: per-row 1-based start and
    optional length, NULL operand/start/length -> NULL. Shared by the SQL
    runner and the column-algebra evaluator so the two host paths cannot
    diverge. Constant parameters (the common, literal case) take the
    vectorized ``str.slice`` path."""
    nulls = s.isna() | starts.isna()
    if lens is not None:
        nulls = nulls | lens.isna()
    nl = nulls.to_numpy(dtype=bool)
    sv = s.astype(object).astype(str)
    su = starts[~nulls].unique()
    lu = None if lens is None else lens[~nulls].unique()
    if len(su) <= 1 and (lu is None or len(lu) <= 1):
        st0 = max(int(su[0]) - 1, 0) if len(su) else 0
        if lens is not None:
            n = int(lu[0]) if lu is not None and len(lu) else 0
            res = sv.str.slice(st0, st0 + n)
        else:
            res = sv.str.slice(st0)
        res = res.astype(object)
        res[nl] = None
        return res
    out: List[Any] = []
    for i in range(len(sv)):
        if nl[i]:
            out.append(None)
            continue
        x = sv.iloc[i]
        st0 = max(int(starts.iloc[i]) - 1, 0)
        if lens is not None:
            out.append(x[st0:st0 + int(lens.iloc[i])])
        else:
            out.append(x[st0:])
    res = pd.Series(out, index=s.index, dtype=object)
    res[nl] = None
    return res


def like_pattern_to_regex(pattern: str) -> str:
    """SQL LIKE pattern -> an equivalent regex (``%`` -> ``.*``,
    ``_`` -> ``.``, everything else literal). Unanchored — use
    :func:`compile_like_regex` for matching."""
    out = []
    for ch in pattern:
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
    return "".join(out)


def compile_like_regex(pattern: str) -> "re.Pattern":
    r"""THE compiled regex every LIKE evaluator (host select runner,
    device dictionary LUTs, pandas column algebra) matches with. Anchored
    with ``\A...\Z`` — ``$`` would also match just before a trailing
    newline, so the three evaluators could diverge on values like
    ``"red\n"``. DOTALL because SQL's ``%``/``_`` match
    any character INCLUDING newlines (``'a\nb' LIKE 'a%'`` is TRUE)."""
    return re.compile(
        r"\A" + like_pattern_to_regex(pattern) + r"\Z", re.DOTALL
    )


def _cast_series(s: pd.Series, tp: pa.DataType) -> pd.Series:
    from fugue_tpu.dataframe.arrow_utils import cast_table

    arr = pa.Array.from_pandas(s)
    table = pa.Table.from_arrays([arr], names=["_c"])
    out = cast_table(table, Schema([pa.field("_c", tp)]))
    return out.column(0).to_pandas()


def eval_filter(df: pd.DataFrame, condition: ColumnExpr) -> pd.DataFrame:
    assert_or_throw(not is_agg(condition), ValueError("WHERE can't aggregate"))
    if len(df) == 0:
        return df
    mask = _bool_series(eval_expr(df, condition)).fillna(False).astype(bool)
    return df[mask.to_numpy()]


def eval_assign(df: pd.DataFrame, **columns: ColumnExpr) -> pd.DataFrame:
    out = df.copy(deep=False)
    for name, expr in columns.items():
        assert_or_throw(not is_agg(expr), ValueError("assign can't aggregate"))
        out[name] = eval_expr(df, expr) if len(df) > 0 else \
            _empty_typed_series(expr, df)
    return out

def _empty_typed_series(expr: ColumnExpr, df: pd.DataFrame) -> pd.Series:
    return pd.Series([], dtype=object)



def _apply_agg(
    grouped: Any, func: str, col: str, distinct: bool
) -> pd.Series:
    f = func.lower()
    if f == "count":
        if distinct:
            return grouped[col].nunique(dropna=True)
        return grouped[col].count()
    if f in ("avg", "mean"):
        if distinct:
            return grouped[col].agg(lambda s: s.drop_duplicates().mean())
        return grouped[col].mean()
    if f == "sum":
        if distinct:
            return grouped[col].agg(
                lambda s: s.drop_duplicates().sum(min_count=1)
            )
        return grouped[col].sum(min_count=1)  # all-null -> NULL like SQL
    if f == "min":
        return grouped[col].min()
    if f == "max":
        return grouped[col].max()
    if f in VARIANCE_FUNCS:
        ddof, fn2 = variance_ddof(f), variance_stat(f)
        if distinct:
            return grouped[col].agg(
                lambda s: getattr(s.drop_duplicates(), fn2)(ddof=ddof)
            )
        return getattr(grouped[col], fn2)(ddof=ddof)
    if f == "median":
        if distinct:
            return grouped[col].agg(lambda s: s.drop_duplicates().median())
        return grouped[col].median()
    if f == "first":
        # .first() would skip nulls; we want the literal first row value
        return grouped[col].agg(lambda s: s.iloc[0] if len(s) > 0 else None)
    if f == "last":
        return grouped[col].agg(lambda s: s.iloc[-1] if len(s) > 0 else None)
    raise NotImplementedError(f"aggregation {func} not supported")


def _global_agg(df: pd.DataFrame, func: str, col: str, distinct: bool) -> Any:
    f = func.lower()
    s = df[col]
    if f == "count":
        return s.nunique(dropna=True) if distinct else s.count()
    if f in ("avg", "mean"):
        return s.drop_duplicates().mean() if distinct else s.mean()
    if f == "sum":
        if distinct:
            return s.drop_duplicates().sum(min_count=1)
        return s.sum(min_count=1)
    if f == "min":
        return s.min()
    if f == "max":
        return s.max()
    if f in VARIANCE_FUNCS:
        vals = s.drop_duplicates() if distinct else s
        return getattr(vals, variance_stat(f))(ddof=variance_ddof(f))
    if f == "median":
        vals = s.drop_duplicates() if distinct else s
        return vals.median()
    if f == "first":
        return s.iloc[0] if len(s) > 0 else None
    if f == "last":
        return s.iloc[-1] if len(s) > 0 else None
    raise NotImplementedError(f"aggregation {func} not supported")


def eval_aggregate(
    df: pd.DataFrame,
    group_names: List[str],
    aggs: Dict[str, ColumnExpr],
) -> pd.DataFrame:
    """Group by ``group_names`` (empty = global) and compute named
    aggregations. Each agg expression must be a single aggregation function
    whose argument is any non-agg expression."""
    work = df.copy(deep=False)
    plans: List[Tuple[str, str, str, bool]] = []  # (out_name, func, tmp_col, distinct)
    for i, (out_name, expr) in enumerate(aggs.items()):
        assert_or_throw(
            isinstance(expr, _FuncExpr) and expr.is_aggregation and len(expr.args) == 1,
            ValueError(f"{expr} is not a simple aggregation"),
        )
        arg = expr.args[0]
        tmp = f"_agg_arg_{i}"
        if isinstance(arg, _NamedColumnExpr) and arg.wildcard:
            # count(*): count rows — use a constant column
            work[tmp] = 1
        else:
            work[tmp] = eval_expr(df, arg) if len(df) > 0 else None
        plans.append((out_name, expr.func, tmp, expr.arg_distinct))
    if len(group_names) == 0:
        data = {
            out: [_global_agg(work, func, tmp, distinct)]
            for out, func, tmp, distinct in plans
        }
        return pd.DataFrame(data)
    grouped = work.groupby(group_names, dropna=False, sort=False)
    pieces = {
        out: _apply_agg(grouped, func, tmp, distinct)
        for out, func, tmp, distinct in plans
    }
    res = pd.DataFrame(pieces)
    return res.reset_index()


def _rewrite_having(
    expr: ColumnExpr,
    computed: Dict[str, str],
    extra: Dict[str, ColumnExpr],
) -> ColumnExpr:
    """Replace aggregation subtrees with references to aggregated columns."""
    from fugue_tpu.column.expressions import col as _col

    if isinstance(expr, _FuncExpr) and expr.is_aggregation:
        key = expr.alias("").__uuid__()
        if key in computed:
            return _col(computed[key])
        name = f"_having_{len(extra)}"
        extra[name] = expr.alias(name)
        computed[key] = name
        return _col(name)
    if isinstance(expr, _BinaryOpExpr):
        return _BinaryOpExpr(
            expr.op,
            _rewrite_having(expr.left, computed, extra),
            _rewrite_having(expr.right, computed, extra),
        )
    if isinstance(expr, _UnaryOpExpr):
        return _UnaryOpExpr(expr.op, _rewrite_having(expr.col, computed, extra))
    return expr


def eval_select(
    df: pd.DataFrame,
    columns: SelectColumns,
    where: Optional[ColumnExpr] = None,
    having: Optional[ColumnExpr] = None,
) -> pd.DataFrame:
    """Full SELECT semantics on pandas: WHERE -> projection/aggregation ->
    HAVING -> DISTINCT."""
    # wildcard expansion only needs column NAMES; declare string to avoid an
    # O(rows*cols) arrow conversion here
    cols = columns.replace_wildcard(
        Schema([pa.field(str(c), pa.string()) for c in df.columns])
    ).assert_all_with_names()
    if where is not None:
        df = eval_filter(df, where)
    if not cols.has_agg:
        out = pd.DataFrame(
            {
                c.output_name: (eval_expr(df, c) if len(df) > 0 else
                                pd.Series([], dtype=object))
                for c in cols.all_cols
            }
        )
        if cols.is_distinct:
            out = out.drop_duplicates()
        return out.reset_index(drop=True)
    # aggregation path: group keys are the non-agg output columns.
    # Computed keys materialize under TEMP names so an alias shadowing a
    # source column (SELECT x % 10 AS x, SUM(x) ...) cannot corrupt the
    # aggregate arguments (review-adjacent finding)
    key_names: List[str] = []
    key_rename: Dict[str, str] = {}
    work = df.copy(deep=False)
    for i, k in enumerate(cols.group_keys):
        name = k.output_name
        if (
            isinstance(k, _NamedColumnExpr)
            and k.as_type is None
            and k.name == name
            and name in work.columns
        ):
            key_names.append(name)  # plain passthrough key
            continue
        tmp = f"_gk_{i}"
        while tmp in work.columns:  # never clobber a real input column
            tmp += "_"
        work[tmp] = eval_expr(df, k) if len(df) > 0 else None
        key_rename[tmp] = name
        key_names.append(tmp)
    aggs = {c.output_name: c for c in cols.agg_funcs}
    having_rewritten: Optional[ColumnExpr] = None
    if having is not None:
        # HAVING refers to aggregations: rewrite agg subtrees into column refs
        # over the aggregated output, computing hidden agg columns as needed
        # key by alias-stripped uuid so HAVING's bare agg nodes match
        computed = {c.alias("").__uuid__(): c.output_name for c in cols.agg_funcs}
        extra: Dict[str, ColumnExpr] = {}
        having_rewritten = _rewrite_having(having, computed, extra)
        aggs = dict(aggs, **extra)
    res = eval_aggregate(work, key_names, aggs)
    if key_rename:
        res = res.rename(columns=key_rename)
    if having_rewritten is not None:
        res = eval_filter(res, having_rewritten)
    # order columns as requested
    res = res[[c.output_name for c in cols.all_cols]]
    if cols.is_distinct:
        res = res.drop_duplicates()
    return res.reset_index(drop=True)
