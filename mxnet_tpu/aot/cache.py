"""``CompileCache`` — the persistent, key-addressed executable store.

Layout (crash-safe, the ``CheckpointManager`` discipline)::

    <dir>/entries/<key>/payload.bin    serialized ``jax.export`` artifact
    <dir>/entries/<key>/manifest.json  SHA256 + key components + compile_ms
    <dir>/xla/                         XLA persistent compilation cache

Writers stage under ``entries/<key>.tmp-<pid>-<nonce>`` and publish with
one ``os.replace`` — a process killed mid-write (chaos ``aot.write``
kill drill) can never leave a torn entry that a reader would pick up,
and concurrent writers racing on one key publish-by-rename: the loser
detects the winner's entry and discards its own staging dir (payloads
for one key are bitwise-interchangeable, so any winner is correct).

The **key** is a full fingerprint of everything that makes an executable
valid (:func:`fingerprint`): jaxpr hash, flattened avals + tree
structure, donation, backend/device kind/count, jax+jaxlib versions,
the global precision config, and the ``MXNET_*`` env-knob signature
discovered from tpulint's A002 cache-key corpus — flipping a knob (or
upgrading jaxlib) changes the key, so a stale executable is a MISS,
never silently served.

Serialization tier: ``jax.export`` (StableHLO round-trip; a hit skips
lowering/export/XLA-compilation — one ``make_jaxpr`` trace still runs,
it IS the key — and the XLA persistent cache under ``<dir>/xla`` makes
the remaining backend compile a disk hit too). Where export is unsupported for a
function (e.g. unexportable primitives) the store degrades to plain
trace-and-jit — counted as a miss with a one-time warning, never an
error. ``Compiled.serialize``-style whole-executable payloads slot into
the same entry format when a jaxlib that exposes them is available.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import shutil
import threading
import time
import uuid
import warnings
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from ..base import MXNetError, env_str
from ..resilience import chaos

__all__ = [
    "CompileCache", "CachedJit", "cached_jit", "fingerprint",
    "knob_signature", "get_cache", "set_cache", "reset_default_cache",
    "stats", "reset_stats", "AOT_COUNTERS",
]

_FORMAT = 1
_ADDR_RE = re.compile(r"0x[0-9a-f]+")

#: Counter names surfaced through ``mx.profiler`` (``aot.<name>``) and
#: :func:`stats` — the serve_bench / train_bench / aot_bench row fields.
AOT_COUNTERS = ("aot_hits", "aot_misses", "aot_bytes", "aot_cold_ms_saved",
                "aot_puts", "aot_fallbacks")

_stats_lock = threading.Lock()
_counters: Dict[str, float] = {name: 0 for name in AOT_COUNTERS}
_prof_counters: Dict[str, Any] = {}


def _count(name: str, delta: float = 1) -> None:
    from .. import profiler

    with _stats_lock:
        _counters[name] += delta
        # re-registered into the telemetry registry (gauge ``aot_<name>``
        # via the registry-backed profiler.Counter): the exposition sees
        # AOT traffic whether or not the profiler runs; the chrome
        # counter-event stream still gates on profiler state inside
        c = _prof_counters.get(name)
        if c is None:
            c = _prof_counters[name] = profiler.Counter(
                name=f"aot.{name}")
        c.increment(delta)


def stats() -> Dict[str, float]:
    """Process-wide AOT counter snapshot: hits/misses/bytes moved through
    the store, cold-compile milliseconds avoided (sum of the banked
    ``compile_ms`` of hit entries), publishes, and serialization
    fallbacks."""
    with _stats_lock:
        return dict(_counters)


def reset_stats() -> None:
    with _stats_lock:
        for k in _counters:
            _counters[k] = 0


# ---------------------------------------------------------------------------
# key fingerprint
# ---------------------------------------------------------------------------
_knob_names: Optional[Tuple[str, ...]] = None
_knob_lock = threading.Lock()


def _discover_knob_names() -> Tuple[str, ...]:
    """Every ``MXNET_*`` knob named in a cache-key function anywhere in
    the package — tpulint's A002 corpus (``*cache_key*`` / ``_signature``
    functions), discovered not declared, so a knob added to any jit
    cache key automatically starts invalidating AOT entries too."""
    global _knob_names
    if _knob_names is not None:
        return _knob_names
    with _knob_lock:
        if _knob_names is not None:
            return _knob_names
        import ast

        from ..analysis import ast_rules

        pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        knobs = set()
        for path in ast_rules.iter_py_files([pkg_root]):
            try:
                with open(path, encoding="utf-8") as f:
                    text = f.read()
            except OSError:
                continue
            # cheap pre-filter: only AST-parse files that can contribute
            # (parsing the whole package costs ~1 s per process; a text
            # scan cuts it to the handful of cache-key files)
            if "cache_key" not in text and "_signature" not in text:
                continue
            try:
                tree = ast.parse(text)
            except SyntaxError:
                continue
            knobs |= ast_rules._knobs_from_tree(tree)
        _knob_names = tuple(sorted(knobs))
    return _knob_names


def knob_signature() -> Tuple[Tuple[str, str], ...]:
    """The live ``(knob, value)`` signature over the A002 corpus — part
    of every fingerprint, so flipping e.g. ``MXNET_TPU_STEM_S2D``
    invalidates entries instead of serving a stale conv lowering."""
    return tuple((k, os.environ.get(k, "")) for k in _discover_knob_names())


def jaxlib_version() -> str:
    """Monkeypatchable seam for the version key component (tests pin a
    fake version to prove invalidation without installing anything)."""
    import jaxlib

    return getattr(jaxlib, "__version__", "?")


_backend_memo: Optional[Dict[str, Any]] = None


def reset_backend_memo() -> None:
    """Forget the memoized backend probe — required after anything that
    rebuilds the XLA client (``parallel.dist`` re-initialization with a
    changed world size clears the backends; the stale memo would keep
    fingerprinting against the old device count)."""
    global _backend_memo
    _backend_memo = None


def _backend_components() -> Dict[str, Any]:
    # the device probe (jax.devices + per-device attrs) is memoized —
    # this runs on the per-call dispatch path (CachedJit._sig) and a
    # full probe per served batch would be pure overhead. The memo is
    # keyed on jax.default_backend() (cheap: lru-cached inside jax);
    # reset_backend_memo() drops it when the XLA client is rebuilt.
    global _backend_memo
    backend = jax.default_backend()
    memo = _backend_memo
    if memo is not None and memo["backend"] == backend:
        return memo
    devs = jax.devices()
    _backend_memo = {"backend": backend,
                     "device_kind": str(devs[0].device_kind),
                     "n_devices": len(devs)}
    return _backend_memo


def _aval_of(x):
    from jax.api_util import shaped_abstractify

    return shaped_abstractify(x)


def _mesh_sig():
    """Cheap per-dispatch mesh identity for :meth:`CachedJit._sig` —
    axis names + sizes of the active mesh (no device iteration; this
    runs per served batch / train step). A mid-process mesh change must
    re-resolve, exactly like a knob flip."""
    try:
        from ..parallel.mesh import current_mesh

        mesh = current_mesh()
    except Exception:  # noqa: BLE001
        return None
    if mesh is None:
        return None
    return tuple(zip(mesh.axis_names, mesh.devices.shape))


def _mesh_component() -> Optional[Dict[str, Any]]:
    """Topology of the active mesh (axis names/sizes, device kinds),
    or None off-mesh. Part of every fingerprint: an executable compiled
    for one GSPMD mesh must never be served to another — same jaxpr,
    same avals, completely different partitioning and collectives."""
    try:
        from ..parallel.sharding import mesh_topology

        return mesh_topology()
    except Exception:  # noqa: BLE001 — fingerprinting must never fail
        return None


def _avals_components(args) -> Dict[str, Any]:
    flat, treedef = jax.tree_util.tree_flatten(args)
    return {
        "avals": [[list(getattr(a, "shape", ())),
                   str(getattr(a, "dtype", type(a).__name__)),
                   bool(getattr(a, "weak_type", False))]
                  for a in map(_aval_of, flat)],
        "tree": str(treedef),
    }


def fingerprint(fn: Callable, args, *, label: str,
                donate_argnums: Tuple[int, ...] = (),
                extra=()) -> Tuple[str, Dict[str, Any]]:
    """Compute the cache key for ``fn`` applied to ``args`` (concrete
    arrays or ``ShapeDtypeStruct``s). Returns ``(hex key, components)``.

    Tracing ``fn`` (``jax.make_jaxpr``) is part of key computation — much
    cheaper than XLA compilation, and it makes the key depend on the
    actual program (constants folded at trace time included), not on a
    caller-supplied name that could collide.
    """
    jaxpr = jax.make_jaxpr(fn)(*args)
    # jaxpr text embeds live object reprs for some primitives (e.g.
    # custom_jvp's `jvp_jaxpr_thunk=<function … at 0x7f…>`): scrub the
    # addresses or the hash — and therefore the cache key — would be
    # unique per process, turning every cross-process lookup into a miss
    jaxpr_text = _ADDR_RE.sub("0x0", str(jaxpr))
    components = {
        "format": _FORMAT,
        "label": label,
        "jaxpr_sha256": hashlib.sha256(
            jaxpr_text.encode("utf-8")).hexdigest(),
        "donate": sorted(int(i) for i in donate_argnums),
        "jax": jax.__version__,
        "jaxlib": jaxlib_version(),
        "x64": bool(jax.config.jax_enable_x64),
        "matmul_precision": str(getattr(
            jax.config, "jax_default_matmul_precision", None)),
        "knobs": dict(knob_signature()),
        "mesh": _mesh_component(),
        "extra": list(extra),
    }
    components.update(_backend_components())
    components.update(_avals_components(args))
    key = hashlib.sha256(json.dumps(
        components, sort_keys=True).encode("utf-8")).hexdigest()
    return key, components


# ---------------------------------------------------------------------------
# the on-disk store
# ---------------------------------------------------------------------------
#: the xla dir the LAST CompileCache pointed jax at — how a later store
#: distinguishes "we armed that" (re-point it) from "the user armed
#: that programmatically" (respect it)
_xla_armed_dir: Optional[str] = None


def _our_xla_dirs() -> set:
    dirs = {_xla_armed_dir} - {None}
    env = os.environ.get("MXNET_TPU_AOT_CACHE", "")
    if env:
        # base.py's import-time arming uses the raw env value
        dirs.add(os.path.join(env, "xla"))
        dirs.add(os.path.join(os.path.abspath(env), "xla"))
    return dirs


class CompileCache:
    """Crash-safe on-disk executable store.

    Parameters
    ----------
    directory : str
        Cache root. Created if missing; safe to share between processes
        and concurrent writers (publish-by-rename).
    mode : str
        ``rw`` (default) — read and publish; ``ro`` — read-only (a
        serving fleet warming from a cache baked by CI); ``off`` —
        every lookup misses and nothing is written (the env-driven
        kill switch, ``MXNET_TPU_AOT=off``).
    arm_xla_cache : bool
        Point jax's persistent compilation cache at ``<dir>/xla`` when
        the process has not configured one (``MXNET_COMPILE_CACHE`` /
        ``JAX_COMPILATION_CACHE_DIR`` win) — this is what makes a hit
        skip the backend compile, not just Python tracing.
    """

    _PAYLOAD = "payload.bin"
    _MANIFEST = "manifest.json"

    def __init__(self, directory: str, mode: str = "rw",
                 arm_xla_cache: bool = True):
        if mode not in ("rw", "ro", "off"):
            raise ValueError(
                f"mode must be rw/ro/off, got {mode!r}")
        self._dir = os.path.abspath(directory)
        self.mode = mode
        self._entries = os.path.join(self._dir, "entries")
        os.makedirs(self._entries, exist_ok=True)
        if mode == "rw":  # ro/off consumers never mutate a shared cache
            self._sweep_orphans()
        if arm_xla_cache and mode != "off":
            self._arm_xla_cache()

    @property
    def directory(self) -> str:
        return self._dir

    def _arm_xla_cache(self) -> None:
        global _xla_armed_dir
        if (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or os.environ.get("MXNET_COMPILE_CACHE")):
            return  # the user already chose a cache root — respect it
        target = os.path.join(self._dir, "xla")
        current = jax.config.jax_compilation_cache_dir
        if current == target:
            return  # already pointing at this store
        if current and current not in _our_xla_dirs():
            return  # armed programmatically by the user — respect it
        # `current` is unset, or it points at a PREVIOUS store's xla dir
        # (armed by us or by base.py's import-time env arming): re-point
        # it, or this store's entries would publish while every backend
        # compile keeps hitting the old store's xla tier
        jax.config.update("jax_compilation_cache_dir", target)
        _xla_armed_dir = target
        # cache-everything write thresholds are an rw-store policy; an ro
        # consumer arms the dir for READS of the baked xla tier and
        # leaves jax's default write threshold alone (jax has no
        # read-only cache mode — mount the dir read-only to forbid
        # writes entirely)
        if self.mode == "rw":
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", -1)
        # jax initializes its compilation cache ONCE at the first
        # compile; if this process already compiled something, the dir
        # update above is a silent no-op until the cache object is reset
        # (env-driven flows arm it at import in base.py — this is the
        # programmatic-construction path)
        from jax.experimental.compilation_cache import compilation_cache

        compilation_cache.reset_cache()

    #: staging dirs younger than this are presumed to belong to a LIVE
    #: concurrent writer (a put() completes in seconds; an hour covers
    #: the slowest imaginable TPU payload on the slowest filesystem) —
    #: a fleet member cold-starting against a shared cache must not
    #: yank an in-flight publish out from under a peer
    ORPHAN_TTL_S = 3600.0

    def _sweep_orphans(self) -> None:
        """Drop staging dirs from killed writers (CheckpointManager
        discipline: published entries are the only readable state).
        Age-gated by :data:`ORPHAN_TTL_S` so a concurrent writer's
        in-flight staging dir is never swept."""
        try:
            names = os.listdir(self._entries)
        except OSError:
            return
        now = time.time()
        orphans = []
        for n in names:
            if ".tmp-" not in n:
                continue
            path = os.path.join(self._entries, n)
            try:
                if now - os.path.getmtime(path) < self.ORPHAN_TTL_S:
                    continue
            except OSError:
                continue  # gone already — a peer swept or published it
            orphans.append(n)
            shutil.rmtree(path, ignore_errors=True)
        if orphans:
            warnings.warn(
                f"CompileCache({self._dir}): swept {len(orphans)} orphaned "
                "staging dir(s) from interrupted publishes — published "
                "entries are unaffected", RuntimeWarning, stacklevel=3)

    def _entry_dir(self, key: str) -> str:
        return os.path.join(self._entries, key)

    def keys(self):
        try:
            names = os.listdir(self._entries)
        except OSError:
            return []
        return sorted(n for n in names
                      if ".tmp-" not in n
                      and os.path.isdir(self._entry_dir(n)))

    def __contains__(self, key: str) -> bool:
        return os.path.isfile(
            os.path.join(self._entry_dir(key), self._MANIFEST))

    def load(self, key: str) -> Optional[Tuple[bytes, Dict]]:
        """Read one entry; returns ``(payload, manifest)`` or ``None``.

        A corrupt / truncated entry (checksum mismatch, unreadable
        manifest) warns, is quarantined (``rw`` stores only — removed so
        the next writer can republish; ``ro`` consumers never mutate a
        shared cache), and reads as a miss — never an error. A plain
        ``OSError`` (flaky filesystem) is a miss WITHOUT quarantine:
        it proves nothing about the entry.
        The chaos site ``aot.read`` fires BEFORE the read so injected
        faults propagate to the caller's classifier (a flaky filesystem
        drill), while real corruption stays a warning.
        """
        if self.mode == "off":
            return None
        chaos.site("aot.read", key=key)
        d = self._entry_dir(key)
        mpath = os.path.join(d, self._MANIFEST)
        if not os.path.isfile(mpath):
            return None
        try:
            with open(mpath) as f:
                manifest = json.load(f)
            with open(os.path.join(d, self._PAYLOAD), "rb") as f:
                payload = f.read()
            digest = hashlib.sha256(payload).hexdigest()
            if digest != manifest.get("sha256"):
                raise MXNetError(
                    f"payload checksum mismatch ({len(payload)} bytes, "
                    "torn write or bit rot)")
            return payload, manifest
        except OSError as e:
            # a transient read fault (flaky NFS, EIO) proves nothing
            # about the entry — miss WITHOUT destroying what may be a
            # healthy executable other consumers depend on
            warnings.warn(
                f"CompileCache({self._dir}): could not read entry "
                f"{key[:12]}… ({e}); falling back to a live compile",
                RuntimeWarning, stacklevel=3)
            return None
        except Exception as e:  # noqa: BLE001 — corrupt entry = miss
            warnings.warn(
                f"CompileCache({self._dir}): entry {key[:12]}… is corrupt "
                f"({e}); {'quarantining it and ' if self.mode == 'rw' else ''}"
                "falling back to a live compile", RuntimeWarning,
                stacklevel=3)
            self.quarantine(key)
            return None

    def put(self, key: str, payload: bytes, meta: Dict) -> bool:
        """Publish one entry atomically. Returns True when ``key`` is
        published (by us or a concurrent winner), False when the store
        is not writable or the publish failed (warned, not raised)."""
        if self.mode != "rw":
            return False
        final = self._entry_dir(key)
        if os.path.isdir(final):
            return True  # already published — nothing to do
        tmp = f"{final}.tmp-{os.getpid()}-{uuid.uuid4().hex[:8]}"
        try:
            os.makedirs(tmp)
            with open(os.path.join(tmp, self._PAYLOAD), "wb") as f:
                f.write(payload)
            # the partial-write-then-kill drill point: a kill here leaves
            # a payload with no manifest, in an unpublished staging dir —
            # invisible to readers, swept by a later init once it ages
            # past ORPHAN_TTL_S
            chaos.site("aot.write", key=key)
            manifest = dict(meta)
            manifest.update({
                "format": _FORMAT,
                "key": key,
                "sha256": hashlib.sha256(payload).hexdigest(),
                "bytes": len(payload),
                "created_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                            time.gmtime()),
            })
            with open(os.path.join(tmp, self._MANIFEST), "w") as f:
                json.dump(manifest, f, indent=1)
            try:
                os.replace(tmp, final)
            except OSError:
                if os.path.isdir(final):
                    # lost the publish race — the winner's entry is
                    # equivalent (same key = same program); ours goes
                    shutil.rmtree(tmp, ignore_errors=True)
                    return True
                raise
            _count("aot_puts")
            _count("aot_bytes", len(payload))
            return True
        except Exception as e:  # noqa: BLE001 — publishing is best-effort
            shutil.rmtree(tmp, ignore_errors=True)
            warnings.warn(
                f"CompileCache({self._dir}): failed to publish entry "
                f"{key[:12]}… ({e}); continuing with the live executable",
                RuntimeWarning, stacklevel=3)
            return False

    def quarantine(self, key: str) -> None:
        """Remove a provably-corrupt entry so the next writer can
        republish a good one. A no-op unless this store is ``rw`` — a
        read-only consumer must never mutate a shared cache, even on
        corruption (the owning writer will quarantine on ITS next
        read)."""
        if self.mode == "rw":
            shutil.rmtree(self._entry_dir(key), ignore_errors=True)

    def entry_manifest(self, key: str) -> Optional[Dict]:
        """Manifest of a published entry (no payload read) or None."""
        try:
            with open(os.path.join(self._entry_dir(key),
                                   self._MANIFEST)) as f:
                return json.load(f)
        except (OSError, ValueError):
            return None

    def storage_stats(self) -> Dict[str, int]:
        keys = self.keys()
        total = 0
        for k in keys:
            try:
                total += os.path.getsize(
                    os.path.join(self._entry_dir(k), self._PAYLOAD))
            except OSError:
                pass
        return {"entries": len(keys), "payload_bytes": total}


# ---------------------------------------------------------------------------
# process-default cache (env-driven)
# ---------------------------------------------------------------------------
_UNSET = object()
_default_cache: Any = _UNSET
_default_lock = threading.Lock()


def get_cache() -> Optional[CompileCache]:
    """The process-default store: ``MXNET_TPU_AOT_CACHE=<dir>`` enables
    it, ``MXNET_TPU_AOT=off|rw|ro`` sets the mode (default ``rw``).
    Returns None when disabled — every AOT seam then behaves exactly as
    plain ``jax.jit`` (tier-1's default state)."""
    global _default_cache
    if _default_cache is not _UNSET:
        return _default_cache
    with _default_lock:
        if _default_cache is _UNSET:
            directory = env_str("MXNET_TPU_AOT_CACHE")
            mode = env_str("MXNET_TPU_AOT", "rw").strip().lower() or "rw"
            if mode not in ("rw", "ro", "off"):
                warnings.warn(
                    f"MXNET_TPU_AOT={mode!r} is not one of off/rw/ro; "
                    "using 'rw'", RuntimeWarning, stacklevel=2)
                mode = "rw"
            if directory and mode != "off":
                _default_cache = CompileCache(directory, mode=mode)
            else:
                _default_cache = None
    return _default_cache


def set_cache(cache: Optional[CompileCache]) -> None:
    """Install ``cache`` as the process default (None disables)."""
    global _default_cache
    with _default_lock:
        _default_cache = cache


def reset_default_cache() -> None:
    """Forget the resolved default so the next :func:`get_cache` re-reads
    the environment (tests that monkeypatch ``MXNET_TPU_AOT*``)."""
    global _default_cache
    with _default_lock:
        _default_cache = _UNSET


# ---------------------------------------------------------------------------
# the jit seam
# ---------------------------------------------------------------------------
_warned_unserializable: set = set()


class CachedJit:
    """A ``jax.jit``-shaped callable backed by the persistent store.

    Per argument signature (flattened avals + tree + knob signature +
    backend), the first call resolves ONE executable:

    - store **hit** — deserialize the ``jax.export`` payload and AOT-
      compile its call (donation re-applied; the XLA persistent cache
      makes this compile a disk read). ``fn`` is still traced ONCE by
      :func:`fingerprint` (``make_jaxpr``, the key) — what a hit skips
      is lowering, export and the XLA compile itself;
    - store **miss** — export ``fn``, publish the payload, and use the
      same exported path (so the XLA cache is warmed for future hit
      compiles);
    - export **unsupported** — fall back to plain trace-and-jit,
      counted as a miss plus ``aot_fallbacks``, warned once per label;
    - **no store configured** — delegate to a plain ``jax.jit`` wrapper
      (bit-identical to the pre-AOT behavior, zero bookkeeping).

    Thread-safe; resolved executables are memoized in-process.
    """

    def __init__(self, fn: Callable, *, label: str,
                 donate_argnums=(), cache: Any = "default",
                 static_key=(), in_shardings=None, out_shardings=None):
        self._fn = fn
        self._label = label
        self._donate = tuple(sorted(int(i) for i in donate_argnums))
        self._cache_arg = cache
        self._static = tuple(static_key)
        # GSPMD seam: sharding trees ride every jax.jit call AND the
        # fingerprint (their string form names mesh axes + specs), so a
        # rule-tree change — like a mesh change — lands on a new key
        self._jit_kwargs: Dict[str, Any] = {}
        if in_shardings is not None:
            self._jit_kwargs["in_shardings"] = in_shardings
            self._static += (("in_shardings", str(in_shardings)),)
        if out_shardings is not None:
            self._jit_kwargs["out_shardings"] = out_shardings
            self._static += (("out_shardings", str(out_shardings)),)
        self._execs: Dict[Tuple, Callable] = {}
        self._keys: Dict[Tuple, Optional[str]] = {}
        self._plain: Optional[Callable] = None
        self._lock = threading.Lock()
        #: outcome of the most recent resolution for observability/tests:
        #: "hit" | "miss" | "fallback" | "jit"
        self.last_outcome: Optional[str] = None

    def _cache(self) -> Optional[CompileCache]:
        if self._cache_arg == "default":
            return get_cache()
        return self._cache_arg

    def _sig(self, args) -> Tuple:
        # per-call dispatch path: read shape/dtype straight off array
        # leaves (abstractify only the odd python scalar) — this runs
        # for every served batch / train step when a store is armed.
        # knob_signature() deliberately re-reads the (few) env knobs per
        # call: a mid-process knob flip MUST re-resolve rather than
        # serve the stale executable — the same retrace-on-flip
        # semantic the serving engine's hybridize cache key implements;
        # backend components are memoized (keyed on the live backend)
        flat, treedef = jax.tree_util.tree_flatten(args)
        avals = []
        for a in flat:
            shape = getattr(a, "shape", None)
            dtype = getattr(a, "dtype", None)
            if shape is None or dtype is None:
                a = _aval_of(a)
                shape, dtype = a.shape, a.dtype
            avals.append((tuple(shape), str(dtype),
                          bool(getattr(a, "weak_type", False))))
        return (tuple(avals), treedef, knob_signature(),
                _backend_components()["backend"], _mesh_sig())

    def resolved_key(self, *args) -> Optional[str]:
        """The store key the given signature resolved to (None before
        first call, or when no store is configured) — what a serving
        engine records into its :class:`~mxnet_tpu.aot.WarmupManifest`."""
        if self._cache() is None:
            return None
        return self._keys.get(self._sig(args))

    def __call__(self, *args):
        cache = self._cache()
        if cache is None or cache.mode == "off":
            self.last_outcome = "jit"
            # a no-store warm() banked an AOT-compiled executable under
            # the signature — use it (jit's own dispatch cache is NOT
            # populated by lower().compile(), so falling through to
            # self._plain would recompile). The sig probe only runs when
            # something was prewarmed: the default path stays a plain
            # jax.jit dispatch.
            if self._execs:
                ex = self._execs.get(self._sig(args))
                if ex is not None:
                    return ex(*args)
            ex = self._plain
            if ex is None:
                with self._lock:
                    if self._plain is None:
                        self._plain = jax.jit(
                            self._fn, donate_argnums=self._donate,
                            **self._jit_kwargs)
                    ex = self._plain
            return ex(*args)
        sig = self._sig(args)
        ex = self._execs.get(sig)
        if ex is None:
            with self._lock:
                ex = self._execs.get(sig)
                if ex is None:
                    ex = self._resolve(cache, sig, args)
                    self._execs[sig] = ex
        return ex(*args)

    def warm(self, *args) -> str:
        """Resolve (and AOT-compile) the executable for ``args`` —
        concrete arrays or ``ShapeDtypeStruct``s — without executing it.
        Returns the resolution outcome (``hit``/``miss``/``fallback``/
        ``jit``/``warm`` when already resolved)."""
        cache = self._cache()
        if cache is None or cache.mode == "off":
            sig = self._sig(args)
            with self._lock:
                if sig in self._execs:
                    return "warm"
                if self._plain is None:
                    self._plain = jax.jit(
                        self._fn, donate_argnums=self._donate,
                        **self._jit_kwargs)
                # compile eagerly AND keep the Compiled: lower().compile()
                # does not populate jit's dispatch cache, so discarding
                # it would make the first real call pay the whole
                # compile again (measured: that is exactly what happens)
                self._execs[sig] = self._plain.lower(*args).compile()
            self.last_outcome = "jit"
            return "jit"
        sig = self._sig(args)
        with self._lock:
            if sig in self._execs:
                return "warm"
            self._execs[sig] = self._resolve(cache, sig, args)
        return self.last_outcome or "warm"

    # -- resolution ------------------------------------------------------
    def _resolve(self, cache: CompileCache, sig: Tuple, args) -> Callable:
        key, components = fingerprint(
            self._fn, args, label=self._label,
            donate_argnums=self._donate, extra=self._static)
        self._keys[sig] = key
        loaded = cache.load(key)
        if loaded is not None:
            payload, manifest = loaded
            chaos.site("aot.deserialize", key=key)
            try:
                ex = self._compile_payload(payload, args)
            except Exception as e:  # noqa: BLE001 — bad payload = miss
                warnings.warn(
                    f"CompileCache: entry {key[:12]}… for "
                    f"{self._label!r} failed to deserialize/compile "
                    f"({e}); recompiling live", RuntimeWarning,
                    stacklevel=4)
                cache.quarantine(key)
            else:
                _count("aot_hits")
                _count("aot_bytes", len(payload))
                _count("aot_cold_ms_saved",
                       float(manifest.get("compile_ms", 0.0)))
                self.last_outcome = "hit"
                return ex
        _count("aot_misses")
        return self._compile_and_publish(cache, key, components, args)

    def _compile_payload(self, payload: bytes, args) -> Callable:
        from jax import export as jax_export

        exp = jax_export.deserialize(payload)
        return jax.jit(exp.call, donate_argnums=self._donate
                       ).lower(*args).compile()

    def _compile_and_publish(self, cache: CompileCache, key: str,
                             components: Dict, args) -> Callable:
        jitted = jax.jit(self._fn, donate_argnums=self._donate,
                         **self._jit_kwargs)
        try:
            from jax import export as jax_export

            exp = jax_export.export(jitted)(*args)
            payload = exp.serialize()
        except Exception as e:  # noqa: BLE001 — degrade to live jit
            _count("aot_fallbacks")
            if self._label not in _warned_unserializable:
                _warned_unserializable.add(self._label)
                warnings.warn(
                    f"CompileCache: executable serialization is "
                    f"unavailable for {self._label!r} on this "
                    f"backend/program ({e}); running with live "
                    "trace-and-jit (counted as a miss)",
                    RuntimeWarning, stacklevel=4)
            t0 = time.perf_counter()
            ex = jitted.lower(*args).compile()
            components["compile_ms"] = (time.perf_counter() - t0) * 1e3
            self.last_outcome = "fallback"
            return ex
        t0 = time.perf_counter()
        # compile THROUGH the exported artifact (not the live trace):
        # the resulting XLA program is the one future hits compile, so
        # the persistent XLA cache it populates serves them directly
        try:
            ex = self._compile_payload(payload, args)
        except Exception as e:  # noqa: BLE001 — degrade to live jit
            # export produced a payload its own round-trip cannot
            # compile (version/custom-call quirks) — same degradation
            # as unexportable programs: live jit, counted, not raised
            # out of a served batch; nothing is published (a hit would
            # fail the identical round-trip)
            _count("aot_fallbacks")
            if self._label not in _warned_unserializable:
                _warned_unserializable.add(self._label)
                warnings.warn(
                    f"CompileCache: exported payload for "
                    f"{self._label!r} failed its deserialize/compile "
                    f"round-trip ({e}); running with live trace-and-jit "
                    "(counted as a miss)", RuntimeWarning, stacklevel=4)
            ex = jitted.lower(*args).compile()
            components["compile_ms"] = (time.perf_counter() - t0) * 1e3
            self.last_outcome = "fallback"
            return ex
        compile_ms = (time.perf_counter() - t0) * 1e3
        meta = {"label": self._label, "compile_ms": round(compile_ms, 3),
                "donate": list(self._donate), "components": components}
        cache.put(key, payload, meta)
        self.last_outcome = "miss"
        return ex


def cached_jit(fn: Callable, *, label: str, donate_argnums=(),
               cache: Any = "default", static_key=(),
               in_shardings=None, out_shardings=None) -> CachedJit:
    """``jax.jit`` with the persistent AOT store behind it.

    Drop-in at a compile seam: ``cached_jit(fn, label="trainer.step",
    donate_argnums=(0, 2))`` returns a callable that consults the
    process store (:func:`get_cache`) before compiling and publishes
    after — or behaves exactly like ``jax.jit`` when no store is
    configured. ``static_key`` folds extra caller context into the
    fingerprint; ``cache=`` pins an explicit :class:`CompileCache`.
    ``in_shardings``/``out_shardings`` (GSPMD sharding trees) ride
    every underlying ``jax.jit`` and are folded into the fingerprint
    alongside the active mesh topology, so a mesh or rule-tree change
    never serves a stale executable.
    """
    return CachedJit(fn, label=label, donate_argnums=donate_argnums,
                     cache=cache, static_key=static_key,
                     in_shardings=in_shardings,
                     out_shardings=out_shardings)
