"""The plan cache's DISK tier: AOT-serialized compiled executables.

The PR 9 :class:`~fugue_tpu.optimize.cache.PlanCache` shares compiled
``jax.jit`` handles across engines, but only within one process — a
restarted daemon or a fresh bench process re-pays the full trace + XLA
compile + first dispatch (~2-9 s on this container, the cold-start
residual ROADMAP item 5 names). This module persists the compiled
executables themselves:

- **what is stored** — for every ``_jit_cached`` program whose key is
  process-stable (see :func:`canonical_key_token`), the per-shape
  compiled executable (``jitted.lower(avals).compile()`` serialized via
  :mod:`jax.experimental.serialize_executable`), written through
  ``engine.fs`` under ``fugue.optimize.cache.dir`` — so ``memory://``,
  local dirs and object-store URIs all work, and fleet replicas can
  share one cache;
- **how it is keyed** — the entry id folds the engine's plan signature
  (platform + mesh device ids + every ``fugue.jax.*`` conf value), the
  logical program key, a hash of the program function's source, and the
  argument avals (tree structure + shape/dtype/sharding per leaf).
  Anything that could change the compiled artifact changes the id;
- **how it is invalidated** — every entry carries a header stamped with
  the cache format rev and the jax/jaxlib/python versions. A version
  mismatch or an unreadable (truncated, corrupt) entry is EVICTED — the
  file is removed, the engine recompiles, and a fresh entry replaces it;
  a cache problem is never an execution error;
- **when it is written** — persistence runs on a single background
  worker (miss → compile → dispatch → persist off the critical path).
  The worker re-lowers from avals, so no array data is retained. Writes
  run under the chaos site ``cache.persist`` and a ``cache.persist``
  span; failures are counted (``fugue_engine_exec_cache_persist_total``)
  and logged, never raised.

Hit/miss/evict/corrupt counters ride the existing
``fugue_engine_plan_cache_total`` family under ``tier="disk"`` (the
in-memory handle tier is ``tier="memory"``), with a deserialize-time
histogram (``fugue_engine_exec_cache_deserialize_seconds``).
"""

import logging
import os
import pickle
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, List, NamedTuple, Optional, Tuple

import numpy as np

from fugue_tpu.testing.locktrace import tracked_lock

# bump when the on-disk layout or the keying scheme changes: old entries
# then evict to a recompile instead of deserializing garbage
FORMAT_REV = 1
_MAGIC = b"FGXC1\n"
_SUFFIX = ".jxc"

_log = logging.getLogger("fugue_tpu.optimize.exec_cache")

# ---- conf resolution --------------------------------------------------------
_DEPRECATION_LOGGED = False


def resolve_cache_dir(conf: Any, log: Any = None) -> str:
    """The persistent executable cache dir in effect: the new
    ``fugue.optimize.cache.dir`` key wins; the legacy
    ``fugue.jax.compile.cache`` key (and its ``FUGUE_JAX_COMPILE_CACHE``
    env var) remains an ALIAS that feeds the same disk tier with a
    deprecation note — the two keys can never run divergent caches.
    Empty string = disk tier off."""
    global _DEPRECATION_LOGGED
    from fugue_tpu.constants import (
        FUGUE_CONF_JAX_COMPILE_CACHE,
        FUGUE_CONF_OPTIMIZE_CACHE_DIR,
    )

    try:
        new = str(conf.get(FUGUE_CONF_OPTIMIZE_CACHE_DIR, "") or "").strip()
    except Exception:  # pragma: no cover - conf-less stub
        new = ""
    if new != "":
        return new
    try:
        legacy = str(conf.get(FUGUE_CONF_JAX_COMPILE_CACHE, "") or "").strip()
    except Exception:  # pragma: no cover
        legacy = ""
    if legacy == "":
        legacy = os.environ.get("FUGUE_JAX_COMPILE_CACHE", "").strip()
    if legacy != "" and not _DEPRECATION_LOGGED:
        _DEPRECATION_LOGGED = True
        (log or _log).warning(
            "fugue_tpu: fugue.jax.compile.cache is deprecated — it now "
            "aliases fugue.optimize.cache.dir (the persistent "
            "compiled-executable cache at %s); set "
            "fugue.optimize.cache.dir directly",
            legacy,
        )
    return legacy


def place_jax_compile_cache(checkout: str) -> str:
    """Turn on JAX's own persistent compilation cache for an ENTRY-POINT
    script (``chip_smoke.py``, ``bench.py``) and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
    set here. Unset: ``<checkout>/.jax_cache`` — a FIXED path, because
    the path is part of what a later process must find again. Never call
    this at library import or from engine construction: tier-1 runs would
    fill the checkout with CPU executables."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# ---- stable key encoding ----------------------------------------------------
def canonical_key_token(obj: Any) -> Optional[str]:
    """A deterministic, process-stable string for a program key, or None
    when any component is not a stable primitive (such programs simply
    skip the disk tier — the in-memory tiers still serve them)."""
    if obj is None or isinstance(obj, (bool, int, str)):
        return repr(obj)
    if isinstance(obj, float):
        return repr(obj)
    if isinstance(obj, bytes):
        return "b" + obj.hex()
    if isinstance(obj, np.dtype):
        return f"dt:{obj.str}"
    if isinstance(obj, (tuple, list)):
        parts = [canonical_key_token(x) for x in obj]
        if any(p is None for p in parts):
            return None
        return "(" + ",".join(parts) + ")"  # type: ignore[arg-type]
    if isinstance(obj, frozenset):
        parts = [canonical_key_token(x) for x in obj]
        if any(p is None for p in parts):
            return None
        return "{" + ",".join(sorted(parts)) + "}"  # type: ignore[arg-type]
    return None


_FN_HASHES: "Any" = None
_FN_HASH_LOCK = tracked_lock("optimize.exec_cache._FN_HASH_LOCK")


def fn_source_hash(fn: Callable) -> str:
    """Hash of the program function's source (falls back to bytecode):
    a code change that would produce a different program under the same
    logical key invalidates the entry. Memoized per function object
    (weakly — the jit handles keep live programs' fns alive anyway) so
    the ``inspect.getsource`` file I/O runs once per program, not per
    probe/persist."""
    global _FN_HASHES
    import weakref

    table = _FN_HASHES
    if table is not None:
        # lock-free fast path (dict read under the GIL): the steady
        # state of every dispatch must not serialize on a global lock
        try:
            cached = table.get(fn)
        except TypeError:  # unweakrefable callable: compute uncached
            cached = None
        if cached is not None:
            return cached
    with _FN_HASH_LOCK:
        if _FN_HASHES is None:
            _FN_HASHES = weakref.WeakKeyDictionary()
    import hashlib
    import inspect

    try:
        src = inspect.getsource(fn)
    except Exception:
        code = getattr(fn, "__code__", None)
        src = code.co_code.hex() if code is not None else repr(fn)
    digest = hashlib.blake2b(src.encode(), digest_size=16).hexdigest()
    with _FN_HASH_LOCK:
        try:
            _FN_HASHES[fn] = digest
        except TypeError:
            pass
    return digest


_SHARDING_TOKENS: "Any" = None


def _sharding_token(s: Any) -> str:
    # memoized per sharding object: meshes are long-lived and shared by
    # every column of every frame, and repr-ing the device list per
    # LEAF per DISPATCH would dominate the signature cost
    global _SHARDING_TOKENS
    import weakref

    table = _SHARDING_TOKENS
    if table is not None:
        try:
            tok = table.get(s)
            if tok is not None:
                return tok
        except TypeError:
            pass
    try:
        from jax.sharding import NamedSharding

        if isinstance(s, NamedSharding):
            devs = ",".join(str(d) for d in s.mesh.devices.flat)
            tok = f"ns[{devs}]{s.spec}:{s.memory_kind}"
        else:
            tok = repr(s)
    except Exception:  # pragma: no cover - jax API drift
        tok = repr(s)
    try:
        if _SHARDING_TOKENS is None:
            _SHARDING_TOKENS = weakref.WeakKeyDictionary()
        _SHARDING_TOKENS[s] = tok
    except TypeError:  # pragma: no cover - unweakrefable sharding
        pass
    return tok


class ArgsSignature(NamedTuple):
    """One dispatch's argument signature: a stable token (tree structure
    + per-leaf shape/dtype/sharding) and the abstract args a background
    persist can re-lower from without holding any data."""

    token: str
    lower_args: Tuple[Any, ...]


def args_signature(args: Tuple[Any, ...]) -> Optional[ArgsSignature]:
    """Signature of a program's concrete arguments, or None when a leaf
    is not a committed jax array / numpy scalar / python scalar (the
    disk tier then skips this dispatch — correctness never depends on
    it)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    parts: List[str] = [str(treedef)]
    abstract: List[Any] = []
    for x in leaves:
        if isinstance(x, jax.Array):
            parts.append(
                f"a:{x.shape}:{x.dtype}:{_sharding_token(x.sharding)}"
            )
            abstract.append(
                jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            )
        elif isinstance(x, np.generic):
            arr = np.asarray(x)
            parts.append(f"n:{arr.shape}:{arr.dtype}")
            # value-independent: scalars are dynamic (traced) args
            abstract.append(jax.ShapeDtypeStruct(arr.shape, arr.dtype))
        elif isinstance(x, (bool, int, float)):
            # python scalars trace weak-typed: keep the live value (it
            # is tiny) so re-lowering reproduces the exact weak dtype
            parts.append(f"p:{type(x).__name__}")
            abstract.append(x)
        else:
            return None
    abstract_args = jax.tree_util.tree_unflatten(treedef, abstract)
    return ArgsSignature("|".join(parts), tuple(abstract_args))


# ---- background warm threads ------------------------------------------------
_WARM_THREADS: List[threading.Thread] = []
_WARM_LOCK = tracked_lock("optimize.exec_cache._WARM_LOCK")


def _join_warm_threads() -> None:
    """atexit: a daemon warm thread frozen MID-DESERIALIZE by interpreter
    teardown aborts the process from XLA's C++ ("terminate called
    without an active exception") — join stragglers first, bounded."""
    with _WARM_LOCK:
        threads = list(_WARM_THREADS)
    for t in threads:
        if t.is_alive():
            t.join(timeout=10.0)


def spawn_warm_thread(target: Callable[[], Any]) -> threading.Thread:
    """Start a background executable-warm thread, registered for the
    bounded atexit join above."""
    import atexit

    t = threading.Thread(target=target, daemon=True, name="fugue-exec-warm")
    with _WARM_LOCK:
        if not _WARM_THREADS:
            atexit.register(_join_warm_threads)
        _WARM_THREADS[:] = [x for x in _WARM_THREADS if x.is_alive()]
        _WARM_THREADS.append(t)
    t.start()
    return t


# ---- background persist worker ----------------------------------------------
_WORKER_LOCK = tracked_lock("optimize.exec_cache._WORKER_LOCK")
_WORKER: Optional[ThreadPoolExecutor] = None
_PENDING: List[Any] = []


def _worker() -> ThreadPoolExecutor:
    global _WORKER
    with _WORKER_LOCK:
        if _WORKER is None:
            _WORKER = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="fugue-exec-cache"
            )
        return _WORKER


def flush_persists(timeout: Optional[float] = 60.0) -> None:
    """Block until every scheduled executable persist finished — the
    test/bench synchronization point (a process about to be measured
    cold must not exit before its cache entries are durable)."""
    while True:
        with _WORKER_LOCK:
            pending = [f for f in _PENDING if not f.done()]
            _PENDING[:] = pending
        if not pending:
            return
        for f in pending:
            f.result(timeout=timeout)


class ExecutableDiskCache:
    """One engine's view of the disk tier (the engine supplies fs,
    metrics, obs spans and its plan signature; entries themselves are
    engine-agnostic and shared through the filesystem)."""

    def __init__(self, engine: Any, base_uri: str):
        self._engine = engine
        self._base = str(base_uri or "").strip().rstrip("/")
        self._dir_ready = False
        # per-program key-token memo (fn hashes memoize module-wide in
        # fn_source_hash): computed once per program, not per dispatch
        self._key_tokens: dict = {}

    @property
    def enabled(self) -> bool:
        return self._base != ""

    @property
    def base_uri(self) -> str:
        return self._base

    # ---- keying ----------------------------------------------------------
    def entry_id(
        self, plan_sig: str, key: Any, fn: Callable, aval_token: str
    ) -> Optional[str]:
        """Deterministic entry id, or None for disk-ineligible keys."""
        try:
            memo = self._key_tokens.get(key, False)
        except TypeError:  # unhashable key: certainly not disk-stable
            return None
        if memo is False:
            memo = canonical_key_token(key)
            self._key_tokens[key] = memo
        if memo is None:
            return None
        from fugue_tpu.utils.hash import to_uuid

        return to_uuid(plan_sig, memo, fn_source_hash(fn), aval_token)

    def entry_uri(self, plan_sig: str, eid: str) -> str:
        # the plan-signature prefix makes warm scans cheap: a daemon
        # pre-warm lists the dir and reads only its own engine's entries
        return self._engine.fs.join(
            self._base, f"{plan_sig[:8]}-{eid}{_SUFFIX}"
        )

    # ---- load ------------------------------------------------------------
    def load(self, uri: str) -> Tuple[str, Optional[Any], Optional[dict]]:
        """Deserialize one entry: ``("hit", compiled, meta)``, or
        ``("miss", None, None)`` when absent, ``("evict", ...)`` on a
        version mismatch, ``("corrupt", ...)`` on an unreadable entry —
        the latter two remove the file so the recompile's fresh persist
        replaces it."""
        import jax
        import jaxlib

        fs = self._engine.fs
        try:
            if not fs.exists(uri):
                return "miss", None, None
            blob = fs.read_bytes(uri)
        except Exception:
            return "miss", None, None
        try:
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            entry = pickle.loads(blob[len(_MAGIC):])
            meta = entry["meta"]
        except Exception:
            self._evict(uri)
            return "corrupt", None, None
        py = f"{sys.version_info[0]}.{sys.version_info[1]}"
        if (
            meta.get("rev") != FORMAT_REV
            or meta.get("jax") != jax.__version__
            or meta.get("jaxlib") != jaxlib.__version__
            or meta.get("py") != py
        ):
            self._evict(uri)
            return "evict", None, None
        try:
            from jax.experimental import serialize_executable as se

            compiled = se.deserialize_and_load(
                entry["payload"], entry["in_tree"], entry["out_tree"]
            )
        except Exception:
            # serialized against a device topology / runtime this
            # process does not have: unusable here, remove it
            self._evict(uri)
            return "corrupt", None, None
        return "hit", compiled, meta

    def _evict(self, uri: str) -> None:
        try:
            self._engine.fs.rm(uri)
        except Exception:  # pragma: no cover - best-effort cleanup
            pass

    def scan(self, plan_sig: Optional[str] = None) -> List[str]:
        """Entry URIs on disk, optionally filtered to one engine
        signature via the filename prefix."""
        fs = self._engine.fs
        try:
            if not fs.exists(self._base):
                return []
            names = fs.listdir(self._base)
        except Exception:
            return []
        prefix = f"{plan_sig[:8]}-" if plan_sig else ""
        return [
            fs.join(self._base, n)
            for n in sorted(names)
            if n.endswith(_SUFFIX) and n.startswith(prefix)
        ]

    # ---- persist ---------------------------------------------------------
    def schedule_persist(
        self,
        jitted: Any,
        plan_sig: str,
        key: Any,
        fn: Callable,
        sig: ArgsSignature,
        name: str,
        on_done: Optional[Callable[[bool], None]] = None,
    ) -> bool:
        """Queue a background persist of the executable this dispatch
        just compiled. Returns False (nothing queued) for disk-ineligible
        keys. Holds only avals + the jit handle, never array data."""
        eid = self.entry_id(plan_sig, key, fn, sig.token)
        if eid is None:
            return False
        uri = self.entry_uri(plan_sig, eid)
        from fugue_tpu.obs import current_span

        parent = current_span()
        fut = _worker().submit(
            self._persist_now, jitted, plan_sig, key,
            fn_source_hash(fn), sig, name, uri, parent, on_done,
        )
        with _WORKER_LOCK:
            # prune settled futures on append: a long-lived daemon
            # schedules persists forever and nothing else may ever call
            # flush_persists
            _PENDING[:] = [f for f in _PENDING if not f.done()]
            _PENDING.append(fut)
        return True

    def _persist_now(
        self,
        jitted: Any,
        plan_sig: str,
        key: Any,
        fn_hash: str,
        sig: ArgsSignature,
        name: str,
        uri: str,
        parent_span: Any,
        on_done: Optional[Callable[[bool], None]],
    ) -> None:
        import jax
        import jaxlib

        from fugue_tpu.obs import activate, start_span
        from fugue_tpu.testing.faults import fault_point

        ok = False
        try:
            with activate(parent_span):
                with start_span("cache.persist", program=name, uri=uri):
                    # re-lower from avals: hits jax's in-memory lowering/
                    # compilation caches right after the jit dispatch
                    # compiled, so this is cheap and holds no data
                    compiled = jitted.lower(*sig.lower_args).compile()
                    from jax.experimental import serialize_executable as se

                    payload, in_tree, out_tree = se.serialize(compiled)
                    entry = {
                        "meta": {
                            "rev": FORMAT_REV,
                            "jax": jax.__version__,
                            "jaxlib": jaxlib.__version__,
                            "py": (
                                f"{sys.version_info[0]}."
                                f"{sys.version_info[1]}"
                            ),
                            "plan_sig": plan_sig,
                            "key": key,
                            # folded into the filename uuid AND stored
                            # here: the warm scan must register entries
                            # under the same fn-aware in-memory key the
                            # dispatch path computes, or a source change
                            # could serve a stale warm-loaded executable
                            "fn_hash": fn_hash,
                            "aval_token": sig.token,
                            "program": name,
                            "created_at": time.time(),
                        },
                        "payload": payload,
                        "in_tree": in_tree,
                        "out_tree": out_tree,
                    }
                    blob = _MAGIC + pickle.dumps(entry)
                    fs = self._engine.fs
                    if not self._dir_ready:
                        fs.makedirs(self._base, exist_ok=True)
                        self._dir_ready = True
                    fault_point("cache.persist", uri)
                    fs.write_file_atomic(uri, lambda fp: fp.write(blob))
                    ok = True
        except Exception as ex:
            # a failing persist degrades warm starts, never this run
            (getattr(self._engine, "log", None) or _log).warning(
                "fugue_tpu exec-cache: persisting %s to %s failed "
                "(%s: %s); execution unaffected",
                name, uri, type(ex).__name__, ex,
            )
        finally:
            if on_done is not None:
                try:
                    on_done(ok)
                except Exception:  # pragma: no cover - counter callback
                    pass
