"""Column-algebra evaluation on device: masked jnp arrays, Kleene logic.

The JAX lowering of the same expression tree the pandas evaluator interprets
(BASELINE: "FugueSQL group-by aggregates lower to segment_sum/segment_max
scans on device") — select/filter/assign run as jit-compiled elementwise
programs over mesh-sharded columns; XLA fuses the chain into the surrounding
ops (HBM-bandwidth-friendly: one pass).

String columns participate through their dictionary encoding: predicates
(=, <>, <, <=, >, >=, LIKE, IN-as-OR) are resolved against a shared
lexicographic vocabulary built on the host from the SMALL dictionaries,
then executed as int32 lookup-table gathers + numeric compares on device
(the dictionaries never leave the host; only code arrays ride the mesh).
Because the lookup tables are baked into traced programs as constants,
jit cache keys at the call sites must include ``dict_fingerprint``.
"""

from typing import (
    Any,
    Dict,
    Iterator,
    List,
    Mapping,
    NamedTuple,
    Optional,
    Tuple,
    Union,
)

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from fugue_tpu.column.expressions import (
    ColumnExpr,
    _BinaryOpExpr,
    _FuncExpr,
    _LitColumnExpr,
    _NamedColumnExpr,
    _UnaryOpExpr,
)
from fugue_tpu.column.pandas_eval import compile_like_regex
from fugue_tpu.jax_backend.blocks import JaxBlocks, JaxColumn
from fugue_tpu.utils.assertion import assert_or_throw

# a masked value: (values, mask) — mask None means all-valid
Masked = Tuple[jnp.ndarray, Optional[jnp.ndarray]]


class _Str(NamedTuple):
    """A dictionary-encoded string value during device evaluation."""

    codes: jnp.ndarray
    mask: Optional[jnp.ndarray]
    dictionary: np.ndarray  # host-resident decode table


class _StrLit(NamedTuple):
    value: str


_Value = Union[Masked, _Str, _StrLit]

_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")

# caps for host-built pairwise-dictionary tables (dynamic LIKE LUTs,
# composed CONCAT dictionaries): beyond this the host work/memory stops
# being "proportional to the dictionaries" and the host runner wins
_MAX_PAIR_LUT = 1 << 20
_MAX_COMPOSED_DICT = 1 << 18


def _like_literal(operand: "_Str", pattern: str, negated: bool) -> Masked:
    """LIKE against one literal pattern: a 1D dictionary LUT + gather.
    The LUT rows come from the SAME anchored regex helper the host
    evaluators use, so device and host can never diverge on values like
    a trailing newline."""
    rx = compile_like_regex(pattern)
    d = operand.dictionary
    lut = np.fromiter(
        (rx.fullmatch(str(x)) is not None for x in d),
        dtype=bool,
        count=len(d),
    )
    if len(lut) == 0:
        lut = np.zeros(1, dtype=bool)
    hit = jnp.asarray(lut)[jnp.clip(operand.codes, 0, len(lut) - 1)]
    if negated:
        hit = ~hit
    return hit, operand.mask


def _valid(m: Masked) -> jnp.ndarray:
    v, mask = m
    if mask is None:
        return jnp.ones(v.shape, dtype=jnp.bool_)
    return mask


def eval_expr(
    cols: Dict[str, Masked],
    expr: ColumnExpr,
    nrows: int,
    dicts: Optional[Dict[str, np.ndarray]] = None,
) -> Masked:
    res = _eval(cols, expr, nrows, dicts or {})
    if isinstance(res, (_Str, _StrLit)):
        assert_or_throw(
            isinstance(res, _Str) and expr.as_type is None,
            NotImplementedError("string-valued expression on device"),
        )
        return (res.codes, res.mask)  # type: ignore[union-attr]
    if expr.as_type is not None:
        res = _cast(res, expr.as_type)
    return res


def _eval(
    cols: Dict[str, Masked],
    expr: ColumnExpr,
    nrows: int,
    dicts: Dict[str, np.ndarray],
) -> _Value:
    if isinstance(expr, _NamedColumnExpr):
        assert_or_throw(
            expr.name in cols, ValueError(f"{expr.name} not available on device")
        )
        v, m = cols[expr.name]
        if expr.name in dicts:
            return _Str(v, m, dicts[expr.name])
        return (v, m)
    if isinstance(expr, _LitColumnExpr):
        v = expr.value
        if v is None:
            return jnp.zeros((nrows,)), jnp.zeros((nrows,), dtype=jnp.bool_)
        if isinstance(v, str):
            return _StrLit(v)
        assert_or_throw(
            isinstance(v, (int, float, bool)),
            ValueError(f"literal {v!r} not supported on device"),
        )
        return jnp.full((nrows,), v), None
    if isinstance(expr, _UnaryOpExpr):
        inner = _eval(cols, expr.col, nrows, dicts)
        if expr.op in ("IS_NULL", "NOT_NULL"):
            if isinstance(inner, _StrLit):
                raise NotImplementedError("IS NULL on a string literal")
            if isinstance(inner, _Str):
                inner = (inner.codes, inner.mask)
            if expr.op == "IS_NULL":
                return (~_valid(inner)), None
            return _valid(inner), None
        if isinstance(inner, (_Str, _StrLit)):
            raise NotImplementedError(f"unary {expr.op} on strings")
        iv, im = inner
        if expr.op == "-":
            return -iv, im
        if expr.op == "~":
            return ~iv.astype(jnp.bool_), im
        raise NotImplementedError(f"unary {expr.op} on device")
    if isinstance(expr, _BinaryOpExpr):
        left = _eval(cols, expr.left, nrows, dicts)
        right = _eval(cols, expr.right, nrows, dicts)
        if isinstance(left, (_Str, _StrLit)) or isinstance(
            right, (_Str, _StrLit)
        ):
            return _str_compare(expr.op, left, right, nrows)
        return _binary(expr.op, left, right)
    if isinstance(expr, _FuncExpr) and not expr.is_aggregation:
        f = expr.func.lower()
        if f == "coalesce":
            raws = [_eval(cols, a, nrows, dicts) for a in expr.args]
            if any(isinstance(a, (_Str, _StrLit)) for a in raws):
                raise NotImplementedError("COALESCE over strings on device")
            args = [a for a in raws if isinstance(a, tuple)]
            out_v, _ = args[0]
            out_m = _valid(args[0])
            for a in args[1:]:
                av, _am = a
                out_v = jnp.where(out_m, out_v, av)
                out_m = out_m | _valid(a)
            return out_v, out_m
        if f == "like":
            operand = _eval(cols, expr.args[0], nrows, dicts)
            pat = expr.args[1]
            neg = expr.args[2]
            assert_or_throw(
                isinstance(operand, _Str)
                and isinstance(neg, _LitColumnExpr),
                NotImplementedError("LIKE needs a string column"),
            )
            if isinstance(pat, _LitColumnExpr) and isinstance(
                pat.value, str
            ):
                return _like_literal(operand, pat.value, bool(neg.value))
            # dynamic pattern COLUMN: the result depends only on the
            # (value code, pattern code) pair — one host-built 2D LUT
            # over the two dictionaries, one device gather
            pv = _eval(cols, pat, nrows, dicts)
            if isinstance(pv, _StrLit):
                return _like_literal(operand, pv.value, bool(neg.value))
            assert_or_throw(
                isinstance(pv, _Str),
                NotImplementedError("LIKE pattern must be a string"),
            )
            do, dp = operand.dictionary, pv.dictionary
            no, np_ = max(len(do), 1), max(len(dp), 1)
            assert_or_throw(
                no * np_ <= _MAX_PAIR_LUT,
                NotImplementedError("dynamic LIKE dictionaries too large"),
            )
            lut2 = np.zeros((no, np_), dtype=bool)
            for j, p in enumerate(dp):
                rxp = compile_like_regex(str(p))
                lut2[: len(do), j] = np.fromiter(
                    (rxp.fullmatch(str(x)) is not None for x in do),
                    dtype=bool,
                    count=len(do),
                )
            flat = jnp.asarray(lut2.reshape(-1))
            oi = jnp.clip(operand.codes, 0, no - 1)
            pj = jnp.clip(pv.codes, 0, np_ - 1)
            hit = flat[oi * np_ + pj]
            if neg.value:
                hit = ~hit
            return hit, _and_masks(operand.mask, pv.mask)
        if f == "case_when":
            raws = [_eval(cols, a, nrows, dicts) for a in expr.args]
            if any(isinstance(a, (_Str, _StrLit)) for a in raws):
                raise NotImplementedError("string CASE branches on device")
            default = raws[-1]
            out_v, _ = default
            out_valid = _valid(default)
            # first-match-wins: apply branches in REVERSE so earlier
            # conditions overwrite later ones
            for i in range(len(raws) - 2, 0, -2):
                cond, val = raws[i - 1], raws[i]
                cv, _cm = cond
                match = cv.astype(jnp.bool_) & _valid(cond)
                vv, _vm = val
                out_v = jnp.where(match, vv, out_v)
                out_valid = jnp.where(match, _valid(val), out_valid)
            # a NULL-literal default is float64 zeros but contributes no
            # VALUES — don't let it promote int branches to float
            vtypes = [
                raws[i][0].dtype for i in range(1, len(raws) - 1, 2)
            ]
            last = expr.args[-1]
            if not (
                isinstance(last, _LitColumnExpr) and last.value is None
            ):
                vtypes.append(default[0].dtype)
            if vtypes:
                out_v = out_v.astype(jnp.result_type(*vtypes))
            return out_v, out_valid
        if f in _DEV_NUM_UNARY:
            v, m = _num_arg(_eval(cols, expr.args[0], nrows, dicts))
            out = _DEV_NUM_UNARY[f](v)
            if f in ("floor", "ceil", "ceiling", "sign"):
                # int64 result; NaN inputs must become NULL, not garbage
                valid = (
                    jnp.ones(out.shape, dtype=jnp.bool_) if m is None else m
                )
                if jnp.issubdtype(out.dtype, jnp.floating):
                    valid = valid & ~jnp.isnan(out)
                    out = jnp.where(valid, out, jnp.zeros_like(out))
                return out.astype(jnp.int64), valid
            return out, m
        if f == "round":
            v, m = _num_arg(_eval(cols, expr.args[0], nrows, dicts))
            digits = _dev_scalar(expr.args, 1, 0)
            return jnp.round(v.astype(jnp.float64), int(digits)), m
        if f in ("power", "pow"):
            lv, lm = _num_arg(_eval(cols, expr.args[0], nrows, dicts))
            rv, rm = _num_arg(_eval(cols, expr.args[1], nrows, dicts))
            m = _and_masks(lm, rm)
            return lv.astype(jnp.float64) ** rv.astype(jnp.float64), m
        if f == "mod":
            lv, lm = _num_arg(_eval(cols, expr.args[0], nrows, dicts))
            rv, rm = _num_arg(_eval(cols, expr.args[1], nrows, dicts))
            # truncated modulo (sign of dividend), matching the host
            # runners; x % 0 is NULL
            m = _and_masks(lm, rm)
            nz = rv != 0
            m = nz if m is None else (m & nz)
            return jnp.fmod(lv, jnp.where(nz, rv, 1)), m
        if f == "nullif":
            a = _eval(cols, expr.args[0], nrows, dicts)
            b = _eval(cols, expr.args[1], nrows, dicts)
            if isinstance(a, (_Str, _StrLit)) or isinstance(
                b, (_Str, _StrLit)
            ):
                eqv, eqm = _str_compare("==", a, b, nrows)
                assert_or_throw(
                    isinstance(a, _Str),
                    NotImplementedError("NULLIF on a string literal"),
                )
                eq = eqv & (
                    jnp.ones((nrows,), jnp.bool_) if eqm is None else eqm
                )
                am = (
                    jnp.ones((nrows,), jnp.bool_)
                    if a.mask is None
                    else a.mask
                )
                return _Str(a.codes, am & ~eq, a.dictionary)
            av, am = a
            bv, bm = b
            eq = (av == bv) & _valid(a) & _valid(b)
            return av, _valid(a) & ~eq
        if f in ("if", "iif"):
            cond = _eval(cols, expr.args[0], nrows, dicts)
            yes = _eval(cols, expr.args[1], nrows, dicts)
            no = _eval(cols, expr.args[2], nrows, dicts)
            if any(isinstance(x, (_Str, _StrLit)) for x in (cond, yes, no)):
                raise NotImplementedError("string IF branches on device")
            cv, _cm = cond
            match = cv.astype(jnp.bool_) & _valid(cond)
            return (
                jnp.where(match, yes[0], no[0]),
                jnp.where(match, _valid(yes), _valid(no)),
            )
        if f in ("length", "len"):
            operand = _eval(cols, expr.args[0], nrows, dicts)
            assert_or_throw(
                isinstance(operand, _Str),
                NotImplementedError("LENGTH needs a string column"),
            )
            d = operand.dictionary
            lut = np.fromiter(
                (len(str(x)) for x in d), dtype=np.int64, count=len(d)
            )
            if len(lut) == 0:
                lut = np.zeros(1, dtype=np.int64)
            return (
                jnp.asarray(lut)[jnp.clip(operand.codes, 0, len(lut) - 1)],
                operand.mask,
            )
        if f in _DICT_TRANSFORMS or f in (
            "substring", "substr", "replace", "concat"
        ):
            return _dict_transform_eval(cols, expr, f, nrows, dicts)
        raise NotImplementedError(f"function {expr.func} on device")
    raise NotImplementedError(f"can't evaluate {expr} on device")


_DEV_NUM_UNARY: Dict[str, Any] = {
    "abs": jnp.abs,
    "floor": jnp.floor,
    "ceil": jnp.ceil,
    "ceiling": jnp.ceil,
    "sqrt": jnp.sqrt,
    "exp": jnp.exp,
    "ln": jnp.log,
    "log": jnp.log,
    "log2": jnp.log2,
    "log10": jnp.log10,
    "sin": jnp.sin,
    "cos": jnp.cos,
    "tan": jnp.tan,
    "sign": jnp.sign,
}

_DICT_TRANSFORMS: Dict[str, Any] = {
    "upper": lambda x: x.upper(),
    "ucase": lambda x: x.upper(),
    "lower": lambda x: x.lower(),
    "lcase": lambda x: x.lower(),
    "trim": lambda x: x.strip(),
    "ltrim": lambda x: x.lstrip(),
    "rtrim": lambda x: x.rstrip(),
    "reverse": lambda x: x[::-1],
}


def _num_arg(v: _Value) -> Masked:
    if isinstance(v, (_Str, _StrLit)):
        raise NotImplementedError("numeric function over strings")
    return v


def _and_masks(
    a: Optional[jnp.ndarray], b: Optional[jnp.ndarray]
) -> Optional[jnp.ndarray]:
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _dev_scalar(args: Any, i: int, default: Any) -> Any:
    if i >= len(args):
        return default
    a = args[i]
    assert_or_throw(
        isinstance(a, _LitColumnExpr)
        and isinstance(a.value, (int, float, str)),
        NotImplementedError("scalar parameter must be a literal on device"),
    )
    return a.value


def _transformed_dictionary(f: str, args: Any, d: np.ndarray) -> np.ndarray:
    """The host-side dictionary transform for a string scalar function —
    codes are untouched, only the decode table changes."""
    sd = [str(x) for x in d]
    if f in _DICT_TRANSFORMS:
        fn = _DICT_TRANSFORMS[f]
        return np.array([fn(x) for x in sd], dtype=object)
    if f in ("substring", "substr"):
        start0 = max(int(_dev_scalar(args, 1, 1)) - 1, 0)
        if len(args) > 2:
            n = int(_dev_scalar(args, 2, 0))
            return np.array(
                [x[start0:start0 + n] for x in sd], dtype=object
            )
        return np.array([x[start0:] for x in sd], dtype=object)
    if f == "replace":
        old = str(_dev_scalar(args, 1, ""))
        new = str(_dev_scalar(args, 2, ""))
        return np.array([x.replace(old, new) for x in sd], dtype=object)
    raise NotImplementedError(f)  # pragma: no cover - callers gate


def _dict_transform_eval(
    cols: Dict[str, Masked],
    expr: "_FuncExpr",
    f: str,
    nrows: int,
    dicts: Dict[str, np.ndarray],
) -> _Value:
    """String scalar functions as pure dictionary rewrites: the codes and
    mask pass through, the decode table is transformed on the host."""
    if f == "concat":
        # any mix of string COLUMNS and literals. One column: the result
        # dictionary is prefix + entry + suffix. Multiple columns: the
        # result dictionary is the (capped) cross product of the column
        # dictionaries and the codes compose in mixed radix — still pure
        # dictionary rewriting, host work proportional to the product of
        # the dictionaries, zero extra device passes.
        parts = [_eval(cols, a, nrows, dicts) for a in expr.args]
        strs = [p for p in parts if isinstance(p, _Str)]
        if len(strs) == 0 and all(isinstance(p, _StrLit) for p in parts):
            return _StrLit("".join(p.value for p in parts))
        if not all(isinstance(p, (_Str, _StrLit)) for p in parts):
            raise NotImplementedError("CONCAT over non-string values")
        if len(strs) == 1:
            src = strs[0]
            idx = parts.index(src)
            pre = "".join(
                p.value for p in parts[:idx]  # type: ignore[union-attr]
            )
            post = "".join(
                p.value for p in parts[idx + 1:]  # type: ignore[union-attr]
            )
            nd = np.array(
                [pre + str(x) + post for x in src.dictionary], dtype=object
            )
            return _Str(src.codes, src.mask, nd)
        # codes in mixed radix, row-major over the columns in order —
        # matching _compose_concat_dictionary's enumeration exactly
        code: Any = None
        mask: Optional[jnp.ndarray] = None
        for p in strs:
            sz = max(len(p.dictionary), 1)
            c = jnp.clip(p.codes, 0, sz - 1)
            code = c if code is None else code * sz + c
            mask = _and_masks(mask, p.mask)
        tmpl = [
            p.value if isinstance(p, _StrLit) else None for p in parts
        ]
        nd = _compose_concat_dictionary(
            tmpl, [p.dictionary for p in strs]
        )
        return _Str(code, mask, nd)
    operand = _eval(cols, expr.args[0], nrows, dicts)
    assert_or_throw(
        isinstance(operand, _Str),
        NotImplementedError(f"{f} needs a string column"),
    )
    nd = _transformed_dictionary(f, expr.args, operand.dictionary)
    return _Str(operand.codes, operand.mask, nd)


def _str_compare(op: str, left: _Value, right: _Value, nrows: int) -> Masked:
    """String comparison via a shared lexicographic vocabulary: each
    side's dictionary (or literal) maps to its rank in the union, then
    the compare runs numerically on device."""
    if op not in _CMP_OPS:
        raise NotImplementedError(f"binary {op} on strings")
    sides = (left, right)
    if not any(isinstance(s, _Str) for s in sides):
        raise NotImplementedError("literal-vs-literal string compare")
    parts = []
    for s in sides:
        if isinstance(s, _Str):
            parts.append(s.dictionary.astype(str))
        elif isinstance(s, _StrLit):
            parts.append(np.array([s.value], dtype=str))
        else:
            raise NotImplementedError("string vs non-string comparison")
    vocab = np.unique(np.concatenate([p.astype(str) for p in parts]))

    def _rank(s: _Value) -> Masked:
        if isinstance(s, _StrLit):
            r = int(np.searchsorted(vocab, s.value))
            return jnp.full((nrows,), r, dtype=jnp.int32), None
        assert isinstance(s, _Str)
        lut = np.searchsorted(vocab, s.dictionary.astype(str)).astype(
            np.int32
        )
        if len(lut) == 0:
            lut = np.zeros(1, dtype=np.int32)
        v = jnp.asarray(lut)[jnp.clip(s.codes, 0, len(lut) - 1)]
        return v, s.mask

    return _binary(op, _rank(left), _rank(right))


def _binary(op: str, left: Masked, right: Masked) -> Masked:
    lv, lm = left
    rv, rm = right
    if op in ("&", "|"):
        la, ra = lv.astype(jnp.bool_), rv.astype(jnp.bool_)
        lvalid, rvalid = _valid(left), _valid(right)
        lf, rf = la & lvalid, ra & rvalid  # null -> False-filled
        if op == "&":
            value = lf & rf
            valid = (lvalid & rvalid) | (lvalid & ~la) | (rvalid & ~ra)
        else:
            value = lf | rf
            valid = (lvalid & rvalid) | (lvalid & la) | (rvalid & ra)
        return value, valid
    both = None
    if lm is not None or rm is not None:
        both = _valid(left) & _valid(right)
    if op == "==":
        return lv == rv, both
    if op == "!=":
        return lv != rv, both
    if op == "<":
        return lv < rv, both
    if op == "<=":
        return lv <= rv, both
    if op == ">":
        return lv > rv, both
    if op == ">=":
        return lv >= rv, both
    if op == "+":
        return lv + rv, both
    if op == "-":
        return lv - rv, both
    if op == "*":
        return lv * rv, both
    if op == "/":
        return jnp.true_divide(lv, rv), both
    raise NotImplementedError(f"binary {op} on device")


def _cast(m: Masked, tp: pa.DataType) -> Masked:
    v, mask = m
    if pa.types.is_floating(tp):
        dtype = tp.to_pandas_dtype()
        return v.astype(dtype), mask
    if pa.types.is_integer(tp):
        return v.astype(tp.to_pandas_dtype()), mask
    if pa.types.is_boolean(tp):
        return v.astype(jnp.bool_), mask
    raise NotImplementedError(f"device cast to {tp}")


def blocks_to_masked(blocks: JaxBlocks) -> Dict[str, Masked]:
    res: Dict[str, Masked] = {}
    for name, col in blocks.columns.items():
        if col.on_device:
            res[name] = (col.data, col.mask)
    return res


def canonicalize_string_column(
    data: jnp.ndarray, dictionary: np.ndarray
) -> Tuple[jnp.ndarray, np.ndarray]:
    """Re-encode codes when a TRANSFORMED decode table contains
    duplicate values (e.g. TRIM collapsing ``"a "`` and ``"a"``):
    code-identity operations — group-by, distinct, joins, sort ranks —
    require one code per distinct string."""
    if len(dictionary) == 0:
        return data, dictionary
    uniq, inverse = np.unique(dictionary.astype(str), return_inverse=True)
    if len(uniq) == len(dictionary):
        return data, dictionary
    lut = jnp.asarray(inverse.astype(np.int32))
    new = jnp.take(lut, jnp.clip(data, 0, len(dictionary) - 1))
    return new, uniq.astype(object)


def finalize_string_result(
    data: jnp.ndarray, dictionary: np.ndarray
) -> Tuple[jnp.ndarray, np.ndarray, Tuple[int, int]]:
    """Canonicalize a transformed string column and derive its code
    stats — the one shared attach path for computed string outputs."""
    data, dictionary = canonicalize_string_column(data, dictionary)
    return data, dictionary, (0, max(len(dictionary) - 1, 0))


class _Dictionaries(Mapping):
    """The decode tables of a frame's device string columns by name, each
    built (``JaxColumn.dictionary``) only when an expression reads it."""

    def __init__(self, blocks: JaxBlocks):
        self._cols = {
            name: col
            for name, col in blocks.columns.items()
            if col.on_device and col.is_string
        }

    def __getitem__(self, name: str) -> np.ndarray:
        return self._cols[name].dictionary

    def __iter__(self) -> Iterator[str]:
        return iter(self._cols)

    def __len__(self) -> int:
        return len(self._cols)


def dicts_of(blocks: JaxBlocks) -> Mapping:
    """Decode tables of the device-resident string columns (host side)."""
    return _Dictionaries(blocks)


def dict_fingerprint(blocks: JaxBlocks) -> Tuple[Any, ...]:
    """A stable key component for jit caches of programs that bake
    string-dictionary lookup tables in as constants: same expression +
    same fingerprint => identical program. Deterministic
    (``HostDictionary.fingerprint``) so the persistent executable cache
    recognizes the same dictionary across process restarts."""
    out = []
    for name in sorted(blocks.columns):
        col = blocks.columns[name]
        if col.on_device and col.is_string:
            out.append((name, len(col.host_dict), col.host_dict.fingerprint))
    return tuple(out)


def can_eval_on_device(expr: ColumnExpr, blocks: JaxBlocks) -> bool:
    """Whether the whole expression tree references only device columns
    and supported ops. String-KINDED results are only allowed when the
    output decode table is statically known (bare refs and
    dictionary-transform chains — the caller re-attaches it via
    ``result_dictionary``); string subtrees under comparisons/LIKE
    always lower."""
    try:
        kind = _check(expr, blocks)
    except NotImplementedError:
        return False
    if kind == "num":
        return True
    return kind == "str" and expr.as_type is None and _dict_chain_ok(expr)


def _compose_concat_dictionary(
    tmpl: List[Optional[str]], dicts_: List[np.ndarray]
) -> np.ndarray:
    """The decode table of a multi-column CONCAT: the cross product of
    the column dictionaries (row-major over the columns in order —
    matching the mixed-radix code composition), with literal fragments
    interleaved per the template (None marks a column slot)."""
    import itertools

    total = 1
    for d in dicts_:
        total *= max(len(d), 1)
    assert_or_throw(
        total <= _MAX_COMPOSED_DICT,
        NotImplementedError("CONCAT dictionaries too large to compose"),
    )
    parts = list(tmpl)
    col_idx = [i for i, t in enumerate(parts) if t is None]
    nd = np.full(total, "", dtype=object)  # empty dicts: all-masked
    for flat, combo in enumerate(itertools.product(*dicts_)):
        for i, v in zip(col_idx, combo):
            parts[i] = str(v)
        nd[flat] = "".join(parts)  # type: ignore[arg-type]
    return nd


def _dict_chain_ok(expr: ColumnExpr) -> bool:
    """Structural mirror of ``_walk_dict`` with no dictionary work —
    ``can_eval_on_device`` uses it so the decode table is only built by
    the callers that actually need it."""
    if isinstance(expr, _NamedColumnExpr):
        return True
    if isinstance(expr, _FuncExpr):
        f = expr.func.lower()
        if f == "nullif":
            return _dict_chain_ok(expr.args[0])
        if f == "concat":
            subs = [
                a for a in expr.args if not isinstance(a, _LitColumnExpr)
            ]
            return len(subs) >= 1 and all(_dict_chain_ok(s) for s in subs)
        if f in _DICT_TRANSFORMS or f in ("substring", "substr", "replace"):
            return _dict_chain_ok(expr.args[0])
    return False


def result_dictionary(
    expr: ColumnExpr, blocks: JaxBlocks
) -> Optional[np.ndarray]:
    """The output decode table of a codes-preserving string expression
    (bare column refs and dictionary-transform chains: UPPER, TRIM,
    SUBSTRING, REPLACE, one-column CONCAT, string NULLIF); None when the
    expression is not such a chain."""
    try:
        if _check(expr, blocks) != "str":
            return None
        return _walk_dict(expr, blocks)
    except NotImplementedError:
        return None


def _walk_dict(expr: ColumnExpr, blocks: JaxBlocks) -> np.ndarray:
    if isinstance(expr, _NamedColumnExpr):
        col = blocks.columns[expr.name]
        assert col.dictionary is not None
        return col.dictionary
    if isinstance(expr, _FuncExpr):
        f = expr.func.lower()
        if f == "concat":
            str_idx = [
                i
                for i, a in enumerate(expr.args)
                if _check(a, blocks) == "str"
            ]
            if len(str_idx) == 1:
                src_i = str_idx[0]
                pre = "".join(
                    a.value  # type: ignore[union-attr]
                    for a in expr.args[:src_i]
                )
                post = "".join(
                    a.value  # type: ignore[union-attr]
                    for a in expr.args[src_i + 1:]
                )
                inner = _walk_dict(expr.args[src_i], blocks)
                return np.array(
                    [pre + str(x) + post for x in inner], dtype=object
                )
            # multi-column: composed cross-product dictionary, SAME
            # enumeration as _eval's mixed-radix code composition
            for i, a in enumerate(expr.args):
                if i not in str_idx and not (
                    isinstance(a, _LitColumnExpr)
                    and isinstance(a.value, str)
                ):
                    raise NotImplementedError("non-literal CONCAT filler")
            tmpl = [
                None if i in str_idx else a.value  # type: ignore[union-attr]
                for i, a in enumerate(expr.args)
            ]
            return _compose_concat_dictionary(
                tmpl, [_walk_dict(expr.args[i], blocks) for i in str_idx]
            )
        if f == "nullif":
            return _walk_dict(expr.args[0], blocks)
        return _transformed_dictionary(
            f, expr.args, _walk_dict(expr.args[0], blocks)
        )
    raise NotImplementedError(str(expr))


def is_string_result(expr: ColumnExpr, blocks: JaxBlocks) -> bool:
    try:
        return _check(expr, blocks) != "num"
    except NotImplementedError:
        return False


def _check(expr: ColumnExpr, blocks: JaxBlocks) -> str:
    """Kind inference mirroring ``_eval`` exactly: returns "num", "str"
    (dictionary column) or "strlit"; raises NotImplementedError for
    anything ``_eval`` would reject."""
    if isinstance(expr, _NamedColumnExpr):
        col = blocks.columns.get(expr.name)
        if col is None or not col.on_device:
            raise NotImplementedError(expr.name)
        return "str" if col.is_string else "num"
    if isinstance(expr, _LitColumnExpr):
        if isinstance(expr.value, str):
            return "strlit"
        if expr.value is not None and not isinstance(
            expr.value, (int, float, bool)
        ):
            raise NotImplementedError(str(expr.value))
        return "num"
    if isinstance(expr, _UnaryOpExpr):
        k = _check(expr.col, blocks)
        if expr.op in ("IS_NULL", "NOT_NULL"):
            if k == "strlit":
                raise NotImplementedError("IS NULL on a string literal")
            return "num"
        if expr.op in ("-", "~"):
            if k != "num":
                raise NotImplementedError(f"unary {expr.op} on strings")
            return "num"
        raise NotImplementedError(expr.op)
    if isinstance(expr, _BinaryOpExpr):
        lk = _check(expr.left, blocks)
        rk = _check(expr.right, blocks)
        if lk == "num" and rk == "num":
            return "num"
        if expr.op in _CMP_OPS and "num" not in (lk, rk) and "str" in (
            lk, rk
        ):
            return "num"
        raise NotImplementedError(f"binary {expr.op} on {lk}/{rk}")
    if isinstance(expr, _FuncExpr) and not expr.is_aggregation:
        f = expr.func.lower()
        if f == "coalesce":
            for a in expr.args:
                if _check(a, blocks) != "num":
                    raise NotImplementedError("COALESCE over strings")
            return "num"
        if f == "like":
            if _check(expr.args[0], blocks) != "str":
                raise NotImplementedError("LIKE needs a string column")
            if not (
                isinstance(expr.args[1], _LitColumnExpr)
                and isinstance(expr.args[1].value, str)
            ):
                # dynamic pattern: any string expression works (the
                # evaluator builds a pairwise-dictionary LUT, capped)
                if _check(expr.args[1], blocks) not in ("str", "strlit"):
                    raise NotImplementedError(
                        "LIKE pattern must be a string"
                    )
            return "num"
        if f == "case_when":
            for a in expr.args:
                if _check(a, blocks) != "num":
                    raise NotImplementedError("string CASE branches")
            return "num"
        if f in _DEV_NUM_UNARY:
            if _check(expr.args[0], blocks) != "num":
                raise NotImplementedError(f"{f} over strings")
            return "num"
        if f == "round":
            if _check(expr.args[0], blocks) != "num":
                raise NotImplementedError("ROUND over strings")
            _check_scalar_lit(expr.args, 1)
            return "num"
        if f in ("power", "pow", "mod"):
            if (
                _check(expr.args[0], blocks) != "num"
                or _check(expr.args[1], blocks) != "num"
            ):
                raise NotImplementedError(f"{f} over strings")
            return "num"
        if f == "nullif":
            lk = _check(expr.args[0], blocks)
            rk = _check(expr.args[1], blocks)
            if lk == "num" and rk == "num":
                return "num"
            if lk == "str" and rk in ("str", "strlit"):
                return "str"
            raise NotImplementedError(f"NULLIF on {lk}/{rk}")
        if f in ("if", "iif"):
            for a in expr.args:
                if _check(a, blocks) != "num":
                    raise NotImplementedError("string IF branches")
            return "num"
        if f in ("length", "len"):
            if _check(expr.args[0], blocks) != "str":
                raise NotImplementedError("LENGTH needs a string column")
            return "num"
        if f in _DICT_TRANSFORMS:
            if _check(expr.args[0], blocks) != "str":
                raise NotImplementedError(f"{f} needs a string column")
            return "str"
        if f in ("substring", "substr", "replace"):
            if _check(expr.args[0], blocks) != "str":
                raise NotImplementedError(f"{f} needs a string column")
            _check_scalar_lit(expr.args, 1)
            _check_scalar_lit(expr.args, 2)
            return "str"
        if f == "concat":
            kinds = [_check(a, blocks) for a in expr.args]
            if any(k == "num" for k in kinds):
                raise NotImplementedError("CONCAT of non-strings")
            if all(k == "strlit" for k in kinds):
                return "strlit"
            # one or more string columns: dictionary rewrite (multiple
            # columns compose a capped cross-product dictionary)
            return "str"
        raise NotImplementedError(expr.func)
    raise NotImplementedError(str(expr))


def _check_scalar_lit(args: Any, i: int) -> None:
    if i < len(args) and not (
        isinstance(args[i], _LitColumnExpr)
        and isinstance(args[i].value, (int, float, str))
    ):
        raise NotImplementedError("scalar parameter must be a literal")
