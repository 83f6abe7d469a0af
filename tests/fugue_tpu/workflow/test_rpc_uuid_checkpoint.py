"""RPC handler determinism hook: handlers hash into workflow task uuids,
so a deterministic checkpoint is REUSED across identical builds with the
same callback and INVALIDATED when the callback changes."""

from typing import Callable, List

import pandas as pd

from fugue_tpu.execution.native_execution_engine import NativeExecutionEngine
from fugue_tpu.rpc.base import (
    EmptyRPCHandler,
    NativeRPCServer,
    RPCFunc,
    to_rpc_handler,
)
from fugue_tpu.workflow import FugueWorkflow


def test_rpc_handler_uuid_deterministic():
    def cb_a(x):
        return x

    def cb_b(x):
        return x + 1

    # same function -> same uuid across wrapper instances (and runs:
    # the hash is source-based, not object-identity-based)
    assert RPCFunc(cb_a).__uuid__() == RPCFunc(cb_a).__uuid__()
    assert to_rpc_handler(cb_a).__uuid__() == to_rpc_handler(cb_a).__uuid__()
    # different body -> different uuid
    assert RPCFunc(cb_a).__uuid__() != RPCFunc(cb_b).__uuid__()
    # class-identity default for stateless handlers
    assert EmptyRPCHandler().__uuid__() == EmptyRPCHandler().__uuid__()
    assert EmptyRPCHandler().__uuid__() != NativeRPCServer().__uuid__()


def test_rpc_handler_uuid_methods_partials_and_fail_closed():
    import functools

    class Holder:
        def cb(self, v):
            return v

    # bound methods hash their underlying function: instance-independent
    assert RPCFunc(Holder().cb).__uuid__() == RPCFunc(Holder().cb).__uuid__()

    def f(a, b):
        return a + b

    # partials fold their bound arguments into the hash
    assert (
        RPCFunc(functools.partial(f, 1)).__uuid__()
        == RPCFunc(functools.partial(f, 1)).__uuid__()
    )
    assert (
        RPCFunc(functools.partial(f, 1)).__uuid__()
        != RPCFunc(functools.partial(f, 2)).__uuid__()
    )
    # no retrievable source (exec'd code) / opaque callables FAIL CLOSED:
    # per-call uuid, so a deterministic checkpoint never wrongly reuses
    ns: dict = {}
    exec("def g(x):\n    return x", ns)
    assert RPCFunc(ns["g"]).__uuid__() != RPCFunc(ns["g"]).__uuid__()

    class Opaque:
        def __call__(self):
            pass

    assert RPCFunc(Opaque()).__uuid__() != RPCFunc(Opaque()).__uuid__()


def test_rpc_handler_uuid_captured_state():
    # closures fold their captured values: same source, different
    # captured config -> different uuid (a stale checkpoint must not
    # be reused after a config change)
    def make(n):
        def cb(v):
            return v * n

        return cb

    assert RPCFunc(make(2)).__uuid__() == RPCFunc(make(2)).__uuid__()
    assert RPCFunc(make(2)).__uuid__() != RPCFunc(make(3)).__uuid__()

    # bound methods fold the instance's __dict__ the same way
    class Conf:
        def __init__(self, threshold):
            self.threshold = threshold

        def cb(self, v):
            return v >= self.threshold

    assert RPCFunc(Conf(1).cb).__uuid__() == RPCFunc(Conf(1).cb).__uuid__()
    assert RPCFunc(Conf(1).cb).__uuid__() != RPCFunc(Conf(2).cb).__uuid__()


def test_rpc_handler_uuid_nested_and_default_state():
    # captured state must fold TRANSITIVELY: a captured inner function's
    # own closure, and values bound through default arguments
    def make(n):
        def inner(x):
            return x + n

        def outer(x):
            return inner(x)

        return outer

    assert RPCFunc(make(1)).__uuid__() == RPCFunc(make(1)).__uuid__()
    assert RPCFunc(make(1)).__uuid__() != RPCFunc(make(2)).__uuid__()

    def make_d(n):
        def cb(x, m=n):
            return x + m

        return cb

    assert RPCFunc(make_d(1)).__uuid__() == RPCFunc(make_d(1)).__uuid__()
    assert RPCFunc(make_d(1)).__uuid__() != RPCFunc(make_d(2)).__uuid__()


def test_rpc_handler_uuid_opaque_state_fails_closed():
    # a captured object with a state-hiding custom __repr__ must not
    # hash by repr: opaque captured state always fails closed
    import functools

    class Cfg:
        def __init__(self, threshold):
            self.threshold = threshold

        def __repr__(self):
            return "Cfg()"  # hides the behavior-relevant state

    def cb(cfg, v):
        return v >= cfg.threshold

    u1 = RPCFunc(functools.partial(cb, Cfg(1))).__uuid__()
    u2 = RPCFunc(functools.partial(cb, Cfg(999))).__uuid__()
    u3 = RPCFunc(functools.partial(cb, Cfg(1))).__uuid__()
    assert u1 != u2
    assert u1 != u3  # opaque state: never reuse, even for equal configs


def _build(engine, callback, calls: List[int], tag: str):
    def expensive(df: pd.DataFrame, announce: Callable) -> pd.DataFrame:
        calls.append(1)
        announce("ran")
        return df

    dag = FugueWorkflow()
    a = dag.df([[1]], "x:long")
    b = a.transform(
        expensive, schema="*", callback=callback
    ).deterministic_checkpoint()
    b.yield_dataframe_as(f"r_{tag}_{len(calls)}", as_local=True)
    return dag


# module-scope sinks: the callbacks must reference them as GLOBALS, not
# closure cells — closure-captured state folds into the handler uuid
# (fail-closed), so a callback closing over a mutating accumulator would
# (correctly) never reuse its checkpoint
hits_a: List[str] = []
hits_b: List[str] = []


def cb_a(v: str) -> None:
    hits_a.append(v)


def cb_b(v: str) -> None:
    hits_b.append("changed-" + v)


def test_changed_callback_invalidates_deterministic_checkpoint(tmp_path):
    engine = NativeExecutionEngine(
        {"fugue.workflow.checkpoint.path": str(tmp_path)}
    )
    hits_a.clear()
    hits_b.clear()
    calls: List[int] = []
    _build(engine, cb_a, calls, "a").run(engine)
    n1 = len(calls)
    assert n1 >= 1 and len(hits_a) >= 1
    # identical DAG with the SAME callback: checkpoint hit, no recompute
    _build(engine, cb_a, calls, "a2").run(engine)
    assert len(calls) == n1
    # a CHANGED callback is a different task: checkpoint must invalidate
    _build(engine, cb_b, calls, "b").run(engine)
    assert len(calls) == n1 + 1
    assert len(hits_b) >= 1


def test_checkpoint_reuse_with_callback_on_memory_uri():
    # the same determinism guarantee straight through a URI checkpoint dir
    from uuid import uuid4

    base = f"memory://rpc-ckpt/{uuid4().hex[:8]}"
    engine = NativeExecutionEngine({"fugue.workflow.checkpoint.path": base})

    def cb(v: str) -> None:
        pass

    calls: List[int] = []
    _build(engine, cb, calls, "m").run(engine)
    n1 = len(calls)
    _build(engine, cb, calls, "m2").run(engine)
    assert len(calls) == n1
