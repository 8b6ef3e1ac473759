"""Streaming dataset service (ISSUE 14): worker fault domain,
exactly-once shard re-dispatch, named resumable cursors, shared-cache
single-writer election, graceful degradation to local decode.

The two acceptance drills both run REAL processes:

- kill-a-decode-worker-mid-epoch: a SIGKILLed worker's unserved range
  is re-dispatched to the survivor exactly once, the epoch completes
  bitwise-identical to the sequential shard union (zero lost, zero
  duplicated batches), and the dead worker is named in a flight dump
  carrying the ``io_service_*`` gauges;
- rank-loss cursor re-split: 4 elastic drill ranks consume a named
  stream, chaos kills rank 2 mid-train, and the re-rendezvoused
  membership resumes the stream from the persisted cursor — the
  consumed union equals the uninterrupted oracle exactly.
"""
import json
import os
import subprocess
import sys
import time
import warnings

import numpy as onp
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DRILL = os.path.join(ROOT, "tests", "dist", "elastic_drill.py")


# ---------------------------------------------------------------------------
# units: named cursors
# ---------------------------------------------------------------------------

def test_cursor_roundtrip_and_default(tmp_path):
    from mxnet_tpu.io.service import StreamCursor, load_cursor, save_cursor

    root = str(tmp_path)
    assert load_cursor(root, "train") is None
    save_cursor(root, StreamCursor("train", epoch=3, frontier=17, world=4))
    cur = load_cursor(root, "train")
    assert (cur.name, cur.epoch, cur.frontier, cur.world) == \
        ("train", 3, 17, 4)
    # names are sanitized onto the filesystem, not trusted
    save_cursor(root, StreamCursor("a/b c", frontier=1))
    assert load_cursor(root, "a/b c").frontier == 1
    assert not any(os.sep in n for n in os.listdir(tmp_path / "cursors"))


def test_local_stream_resplit_union_is_exactly_once(tmp_path):
    """4 members consume two rounds, the cursor commits, membership
    drops to 3 — the re-split union over the whole run is every batch
    exactly once (the contiguous exactly-once prefix contract)."""
    from mxnet_tpu.io.service import ServiceStream, SyntheticSource

    root = str(tmp_path)
    src = SyntheticSource(n_batches=20, batch_size=2, dim=4)
    streams = [ServiceStream(root, cursor="g", member_index=j, world=4,
                             local=True, source=src) for j in range(4)]
    consumed = []
    for _ in range(2):          # two coordinated rounds at world 4
        for s in streams:
            next(s)
            consumed.append(s.last_index)
    streams[0].save_cursor()    # every member agrees: frontier == 8
    assert streams[0].group_frontier() == 8
    # membership change: members 0, 1, 3 re-split at the saved cursor
    survivors = [s.resplit(j, 3) for j, s in
                 enumerate([streams[0], streams[1], streams[3]])]
    for s in survivors:
        assert s.frontier == 8
        for _ in range(4):
            next(s)
            consumed.append(s.last_index)
    assert sorted(consumed) == list(range(20))
    assert len(consumed) == len(set(consumed))
    # exhaustion: every survivor ends in StopIteration at the edge
    for s in survivors:
        with pytest.raises(StopIteration):
            next(s)


def test_stream_rejects_bad_membership(tmp_path):
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.io.service import ServiceStream, SyntheticSource

    src = SyntheticSource(4)
    with pytest.raises(MXNetError):
        ServiceStream(str(tmp_path), member_index=3, world=2,
                      local=True, source=src)
    s = ServiceStream(str(tmp_path), local=True, source=src)
    with pytest.raises(MXNetError):
        s.resplit(2, 2)


def test_stream_without_plan_or_source_is_typed(tmp_path):
    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.io.service import ServiceStream

    with pytest.raises(MXNetError):
        ServiceStream(str(tmp_path))


# ---------------------------------------------------------------------------
# chaos: the consumer retry loop absorbs in-transit faults
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_stream_chaos_fault_in_transit_absorbed_by_retry(tmp_path):
    """``io.stream`` faults a batch in transit: the bounded
    retry/backoff loop absorbs it and the epoch stays bitwise."""
    from mxnet_tpu.io import service as svc
    from mxnet_tpu.resilience import chaos

    root = str(tmp_path)
    src = svc.SyntheticSource(n_batches=6, batch_size=2, dim=4)
    # a fully-served spool with no worker fleet: every batch
    # pre-published, so only the consumer fetch ladder is under test
    with open(os.path.join(root, "plan.json"), "w") as f:
        json.dump({"version": 1, "n_batches": 6, "range_size": 2}, f)
    os.makedirs(os.path.join(root, "epochs", "e0", "spool"))
    os.makedirs(os.path.join(root, "epochs", "e0", "ranges"))
    for i in range(6):
        d, l = src.read(i)
        svc._publish_batch(root, 0, i, d, l)
    s = svc.ServiceStream(root, local_fallback=False)
    out = []
    with chaos.scope("io.stream", fail="transient", times=2):
        for data, _ in s:
            out.append(data)
    assert chaos.stats().get("io.stream", {}).get("raise", 0) == 2
    assert len(out) == 6
    for i, d in enumerate(out):
        assert (d == src.read(i)[0]).all()


# ---------------------------------------------------------------------------
# graceful degradation: the whole service is down
# ---------------------------------------------------------------------------

def test_service_down_degrades_to_local_decode(tmp_path):
    """A root whose every worker heartbeat is stale: the stream warns
    once, decodes in-process, and the epoch is bitwise-correct."""
    from mxnet_tpu.io.service import ServiceStream, SyntheticSource
    from mxnet_tpu.telemetry.registry import get_registry

    root = str(tmp_path)
    src = SyntheticSource(n_batches=6, batch_size=2, dim=4)
    with open(os.path.join(root, "plan.json"), "w") as f:
        json.dump({"version": 1, "n_batches": 6, "range_size": 2}, f)
    hb = os.path.join(root, "heartbeats")
    os.makedirs(hb)
    beat = os.path.join(hb, "rank_0.json")
    with open(beat, "w") as f:
        json.dump({"rank": 0}, f)
    os.utime(beat, (time.time() - 3600, time.time() - 3600))
    os.makedirs(os.path.join(root, "epochs", "e0", "spool"))

    s = ServiceStream(root, source=src, stale_after_s=0.2, poll_s=0.01)
    assert not s.local  # the plan was found: this is a service stream
    with pytest.warns(RuntimeWarning, match="degrading to in-process"):
        out = list(s)
    assert len(out) == 6
    for i, (d, _) in enumerate(out):
        assert (d == src.read(i)[0]).all()
    fams = get_registry().snapshot()["metrics"]
    assert fams["io_service_local_fallback_total"]["series"][0]["value"] >= 6

    # without a source the same death is typed ServiceDown
    from mxnet_tpu.io.service import ServiceDown

    s2 = ServiceStream(root, stale_after_s=0.2, poll_s=0.01,
                       fetch_deadline_s=0.5)
    with pytest.raises(ServiceDown):
        next(s2)


# ---------------------------------------------------------------------------
# THE drill: kill a real decode worker mid-epoch
# ---------------------------------------------------------------------------

def _kill_while_holding_unserved_claim(svc, wid, timeout_s=60.0):
    """SIGKILL worker ``wid`` at the moment it provably holds a claimed
    range with ≥2 batches still unpublished — so the death always
    leaves an unserved range for the exactly-once re-dispatch to
    recover (a kill between ranges would drill nothing)."""
    from mxnet_tpu.io import service as _svc

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        rdir = _svc._ranges_dir(svc.root, 0)
        for name in os.listdir(rdir):
            if ".claim" not in name or not name.endswith(".json"):
                continue
            k = int(name.split(".")[0][1:])
            if os.path.exists(_svc._done_path(svc.root, 0, k)):
                continue
            claim = _svc._read_json(os.path.join(rdir, name))
            if not claim or claim.get("worker") != wid:
                continue
            lo = k * svc.range_size
            hi = min(lo + svc.range_size, svc.n_batches)
            unpublished = sum(
                not os.path.exists(_svc._batch_path(svc.root, 0, i))
                for i in range(lo, hi))
            if unpublished >= 2:
                svc.kill_worker(wid)
                return k
        time.sleep(0.005)
    raise AssertionError(
        f"worker {wid} never held an unserved claim within {timeout_s}s")


@pytest.mark.integration
def test_kill_decode_worker_mid_epoch_exactly_once(tmp_path,
                                                   lockwatch_armed):
    """Acceptance: a real worker process SIGKILLed mid-epoch; the
    survivor absorbs its unserved range via the exactly-once re-dispatch
    marker, the epoch output is bitwise-identical to the sequential
    shard union with zero lost / zero duplicated batches, and the dead
    worker is named in a flight dump carrying the io_service gauges.
    Lockwatch rides along (``MXNET_TPU_LOCKWATCH``) and asserts zero
    observed lock-order cycles through the kill + re-dispatch."""
    from mxnet_tpu.io.service import DatasetService, SyntheticSource
    from mxnet_tpu.telemetry import flight
    from mxnet_tpu.telemetry.registry import get_registry

    fdir = str(tmp_path / "flight")
    flight.arm(fdir)      # the conftest restores the arming afterwards
    src = SyntheticSource(n_batches=30, batch_size=2, dim=4, seed=3,
                          decode_cost_s=0.05)
    svc = DatasetService(str(tmp_path / "root"), src, num_workers=2,
                         range_size=5, heartbeat_s=0.1,
                         stale_after_s=0.6)
    with svc:
        svc.start()
        svc.start_epoch(0)
        # generous fetch deadline: worker spawn pays a multi-second
        # import before the first beat, and tier-1 runs under load
        s = svc.stream(local_fallback=False, fetch_deadline_s=120.0)
        out = [next(s) for _ in range(2)]
        _kill_while_holding_unserved_claim(svc, wid=0)
        out += [next(s) for _ in range(28)]
    # bitwise-identical to the sequential shard union
    ids = []
    for i, (data, label) in enumerate(out):
        d_ref, l_ref = src.read(i)
        assert (data == d_ref).all() and (label == l_ref).all()
        ids.extend(int(v) for v in label[:, 0])
    # zero lost, zero duplicated: the sample-id union is exact
    assert sorted(ids) == list(range(30 * 2))
    fams = get_registry().snapshot()["metrics"]
    red = fams["io_service_ranges_redispatched_total"]["series"]
    assert red and red[0]["value"] >= 1
    lost = fams["io_service_workers_lost_total"]["series"]
    assert any(sr["labels"].get("worker") == "0" for sr in lost)
    dumps = [n for n in os.listdir(fdir) if "io_worker_lost-w0" in n]
    assert dumps, f"no worker-lost flight dump in {os.listdir(fdir)}"
    with open(os.path.join(fdir, dumps[0])) as f:
        payload = json.load(f)
    assert payload["reason"] == "io_worker_lost:w0"
    fams = payload["metrics"]["metrics"]
    for name in ("io_service_workers_live",
                 "io_service_ranges_redispatched_total",
                 "io_service_batches_total"):
        assert name in fams, f"{name} missing from flight metrics"


@pytest.mark.integration
@pytest.mark.chaos
def test_chaos_kill_targeted_worker_epoch_still_completes(tmp_path, monkeypatch):
    """The ``io.worker.<id>`` per-worker chaos variant: every spawned
    worker inherits the armed env, but only worker 1 dies (at its 3rd
    decoded batch) — the survivor finishes the epoch exactly-once."""
    from mxnet_tpu.io.service import DatasetService, SyntheticSource

    monkeypatch.setenv("MXNET_TPU_CHAOS", "io.worker.1=kill:3")
    src = SyntheticSource(n_batches=20, batch_size=2, dim=4, seed=5,
                          decode_cost_s=0.01)
    svc = DatasetService(str(tmp_path / "root"), src, num_workers=2,
                         range_size=4, heartbeat_s=0.1, stale_after_s=0.6)
    with svc:
        svc.start()
        svc.start_epoch(0)
        s = svc.stream(local_fallback=False, fetch_deadline_s=120.0)
        out = [s.read(i) for i in range(20)]
    ids = []
    for i, (data, label) in enumerate(out):
        d_ref, _ = src.read(i)
        assert (data == d_ref).all()
        ids.extend(int(v) for v in label[:, 0])
    assert sorted(ids) == list(range(40))


# ---------------------------------------------------------------------------
# THE drill: rank-loss cursor re-split through the elastic harness
# ---------------------------------------------------------------------------

def _spawn_io_drill(root, io_root, rank, chaos_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("MXNET_TPU_CHAOS", None)
    env.pop("MXNET_TPU_FLIGHT_DIR", None)
    env.pop("MXNET_TPU_IO_SERVICE", None)
    if chaos_env:
        env["MXNET_TPU_CHAOS"] = chaos_env
    cmd = [sys.executable, DRILL, "--root", str(root), "--rank", str(rank),
           "--world", "4", "--steps", "8", "--save-every", "2",
           "--io-root", str(io_root)]
    return subprocess.Popen(cmd, env=env, cwd=ROOT, text=True,
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE)


@pytest.mark.integration
def test_rank_loss_resplits_stream_at_named_cursor(tmp_path):
    """Acceptance: chaos kills rank 2 of 4 mid-train; after the
    re-rendezvous the survivors resume the stream from the persisted
    named cursor (frontier 8, the last coordinated boundary) and the
    resumed consumption union is exactly the uninterrupted oracle's
    suffix — every batch once, none lost, none duplicated."""
    procs = {
        r: _spawn_io_drill(tmp_path / "drill", tmp_path / "io", r,
                           chaos_env=("dist.collective=kill:5" if r == 2
                                      else None))
        for r in range(4)
    }
    results = {}
    for r, p in procs.items():
        stdout, stderr = p.communicate(timeout=150)
        rec = None
        for line in stdout.splitlines():
            if line.startswith("ELASTIC_RESULT "):
                rec = json.loads(line[len("ELASTIC_RESULT "):])
        results[r] = (p.returncode, rec, stderr)
    assert results[2][0] == 137, f"rank 2 must die, got {results[2][0]}"
    resumed = []
    for r in (0, 1, 3):
        rc, rec, err = results[r]
        assert rc == 0 and rec is not None, f"rank {r}: rc={rc}\n{err[-2000:]}"
        io = rec["io"]
        # every consumed batch was bitwise-equal to the source oracle
        assert all(c["ok"] for c in io["consumed"])
        # the final cursor covers the whole effective epoch: 8 batches
        # at world 4 (committed prefix) + 18 at world 3
        assert io["cursor_frontier"] == 26 and io["cursor_world"] == 3
        resumed += [c["idx"] for c in io["consumed"] if c["gen"] == 1]
        # the committed gen-0 prefix is this member's strided assignment
        pre = [c["idx"] for c in io["consumed"]
               if c["gen"] == 0 and c["step"] < 2]
        assert pre == [r, r + 4]
    # the resumed union == the uninterrupted oracle's suffix, exactly
    assert sorted(resumed) == list(range(8, 26))
    assert len(resumed) == len(set(resumed))


# ---------------------------------------------------------------------------
# shared epoch cache: single-writer election + hygiene
# ---------------------------------------------------------------------------

def _counting_factory(counter, n_batches=6, batch=4, h=8, w=8,
                      label_width=1):
    """A deterministic decode stand-in that counts invocations of its
    batch decode (the work the election is supposed to spend once)."""

    class _It:
        def __init__(self):
            self._i = 0

        def __iter__(self):
            return self

        def __next__(self):
            if self._i >= n_batches:
                raise StopIteration
            i = self._i
            self._i += 1
            counter.append(i)
            base = onp.arange(batch * h * w * 3, dtype=onp.uint8)
            data = (base.reshape(batch, h, w, 3) + i).astype(onp.uint8)
            label = onp.full((batch, label_width), float(i), onp.float32)
            return data, label

        def reset(self):
            self._i = 0

        def close(self):
            pass

    return _It


def test_shared_cache_single_writer_election(tmp_path):
    """Two concurrent cold openers of one key: exactly ONE banks, the
    reader streams live decode without writing, both flip to the slab
    and epoch 2 is bitwise-equal with zero further decode."""
    from mxnet_tpu.io.cache import CachedImagePipeline

    src = tmp_path / "src.rec"
    src.write_bytes(b"x" * 64)
    decoded = []
    kw = dict(cache_dir=str(tmp_path / "cache"), source_path=str(src),
              data_shape=(3, 8, 8), batch_size=4)
    p1 = CachedImagePipeline(_counting_factory(decoded), **kw)
    p2 = CachedImagePipeline(_counting_factory(decoded), **kw)
    e1, e2 = [], []
    it1, it2 = iter(p1), iter(p2)
    for _ in range(6):
        e1.append(next(it1))
        e2.append(next(it2))
    for it in (it1, it2):
        with pytest.raises(StopIteration):
            next(it)
    # exactly one writer was elected; the reader decoded live
    assert [p1.is_writer, p2.is_writer].count(True) == 1
    assert len(decoded) == 12  # 6 batches each, NOT banked twice
    assert p1.complete and p2.complete
    # exactly one slab on disk, committed
    kdir = os.path.dirname(p1._meta_path)
    assert os.path.exists(os.path.join(kdir, "data.u8"))
    assert not [n for n in os.listdir(kdir) if ".tmp" in n]
    # epoch 2: both stream the slab bitwise, zero additional decode
    p1.reset(), p2.reset()
    for i in range(6):
        d1, l1 = next(p1)
        d2, l2 = next(p2)
        assert (d1 == e1[i][0]).all() and (d2 == e2[i][0]).all()
        assert (l1 == e1[i][1]).all()
    assert len(decoded) == 12
    p1.close(), p2.close()


def test_shared_cache_breaks_stale_writer_lock(tmp_path):
    """A crashed writer's lock (mtime stopped moving) is broken by the
    next cold opener, which re-elects itself and banks."""
    from mxnet_tpu.io.cache import CachedImagePipeline, cache_key

    src = tmp_path / "src.rec"
    src.write_bytes(b"x" * 64)
    cache = tmp_path / "cache"
    key = cache_key(str(src), 8, 8, 1)
    kdir = cache / key
    kdir.mkdir(parents=True)
    lock = kdir / "writer.lock"
    lock.write_text("{}")
    # stale for the election (> writer_ttl_s) but fresh enough that the
    # open-time sweep keeps it — the _elect break path is under test
    old = time.time() - 30
    os.utime(lock, (old, old))
    decoded = []
    p = CachedImagePipeline(_counting_factory(decoded), cache_dir=str(cache),
                            source_path=str(src), data_shape=(3, 8, 8),
                            batch_size=4, writer_ttl_s=5.0)
    list(p)
    assert p.is_writer and p.complete
    p.close()


def test_sweep_cache_root_hygiene_and_retention(tmp_path):
    """Crashed-writer litter is swept bounded and race-tolerant: stale
    tmp slabs, dead locks, abandoned partial key dirs go; committed
    slabs honor newest-N retention; fresh litter is kept."""
    from mxnet_tpu.io.cache import sweep_cache_root

    root = tmp_path / "cache"
    old = time.time() - 7200

    def make_key(name, committed, extra=(), ages=()):
        k = root / name
        k.mkdir(parents=True)
        if committed:
            (k / "meta.json").write_text('{"n": 1}')
        for n in extra:
            (k / n).write_text("x")
        for n, t in ages:
            os.utime(k / n, (t, t))
        return k

    k_live = make_key("live", True, extra=("data.u8",))
    k_old1 = make_key("old1", True, extra=("data.u8",),
                      ages=(("meta.json", old - 20),))
    k_tmp = make_key("tmpl", True,
                     extra=("data.u8", "data.u8.1.ff.tmp", "writer.lock"),
                     ages=(("data.u8.1.ff.tmp", old), ("writer.lock", old)))
    k_part = make_key("part", False, extra=("data.u8.2.aa.tmp",),
                      ages=(("data.u8.2.aa.tmp", old),))
    os.utime(k_part, (old, old))
    # fresh uncommitted dir (a writer banking RIGHT NOW): must survive
    k_fresh = make_key("fresh", False, extra=("data.u8.3.bb.tmp",))

    with pytest.warns(RuntimeWarning, match="swept shared-cache litter"):
        swept = sweep_cache_root(str(root), keep_complete=2, ttl_s=3600)
    # 2 tmps: the committed dir's stale slab + the abandoned partial's
    # (swept individually before its whole dir goes as a partial)
    assert swept["tmps"] == 2 and swept["locks"] == 1
    assert swept["partials"] == 1 and swept["complete"] == 1
    assert k_live.exists() and k_tmp.exists() and k_fresh.exists()
    assert not k_old1.exists() and not k_part.exists()
    assert not (k_tmp / "data.u8.1.ff.tmp").exists()
    assert not (k_tmp / "writer.lock").exists()
    # idempotent + silent when clean
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        swept2 = sweep_cache_root(str(root), keep_complete=2, ttl_s=3600)
    assert not any(swept2.values())


def test_cache_open_sweeps_shared_root(tmp_path):
    """The sweep runs at every open — a cold start on a littered shared
    root cleans it up before banking."""
    from mxnet_tpu.io.cache import CachedImagePipeline

    root = tmp_path / "cache"
    litter = root / "dead"
    litter.mkdir(parents=True)
    (litter / "data.u8.9.cc.tmp").write_text("x")
    old = time.time() - 7200
    os.utime(litter / "data.u8.9.cc.tmp", (old, old))
    os.utime(litter, (old, old))
    src = tmp_path / "src.rec"
    src.write_bytes(b"x" * 64)
    with pytest.warns(RuntimeWarning, match="swept shared-cache litter"):
        p = CachedImagePipeline(_counting_factory([]), cache_dir=str(root),
                                source_path=str(src), data_shape=(3, 8, 8),
                                batch_size=4)
    assert not litter.exists()
    p.close()


# ---------------------------------------------------------------------------
# DevicePrefetch planned-teardown seam (elastic re-rendezvous)
# ---------------------------------------------------------------------------

def test_device_prefetch_detach_is_clean_stopiteration():
    """detach() mid-stream (the elastic re-rendezvous stopping the
    input plane): staged batches drain, then clean ``StopIteration`` —
    never the dead-feeder ``FatalError``."""
    from mxnet_tpu.io import DevicePrefetch

    def src():
        for i in range(1000):
            if i >= 4:
                time.sleep(0.05)  # the feeder is mid-pull at detach
            yield onp.full((2, 2), i, "float32")

    dp = DevicePrefetch(src(), depth=2)
    first = next(dp)
    assert float(first[0, 0]) == 0.0
    dp.detach()
    drained = 0
    with pytest.raises(StopIteration):
        while True:
            next(dp)
            drained += 1
    assert drained < 999  # the stream really stopped early
    dp.detach()  # idempotent
    with pytest.raises(StopIteration):
        next(dp)  # exhaustion is sticky, still not a FatalError
    dp.close()


def test_device_prefetch_detach_after_exhaustion_keeps_semantics():
    """The other order: natural epoch end first, detach after — the
    PR-4 exhaustion contract is unchanged."""
    from mxnet_tpu.io import DevicePrefetch

    def src():
        yield onp.zeros((1,), "float32")

    dp = DevicePrefetch(src(), depth=2)
    assert len(list(dp)) == 1
    with pytest.raises(StopIteration):
        next(dp)
    dp.detach()
    with pytest.raises(StopIteration):
        next(dp)
    dp.close()


# ---------------------------------------------------------------------------
# telemetry exposition
# ---------------------------------------------------------------------------

def test_io_service_gauges_visible_in_snapshot_and_prometheus(tmp_path):
    from mxnet_tpu.io.service import ServiceStream, SyntheticSource
    from mxnet_tpu.telemetry.registry import get_registry

    from mxnet_tpu.io.cache import _cache_metrics

    src = SyntheticSource(n_batches=2, batch_size=2, dim=4)
    s = ServiceStream(str(tmp_path), local=True, source=src)
    next(s)
    _cache_metrics()  # the shared-cache gauges register at cache open
    reg = get_registry()
    fams = reg.snapshot()["metrics"]
    for name in ("io_service_workers_live",
                 "io_service_ranges_redispatched_total",
                 "io_service_cursor_lag", "io_service_batches_total",
                 "io_service_local_fallback_total",
                 "io_service_cache_hit"):
        assert name in fams, f"{name} missing from snapshot"
    text = reg.prometheus_text()
    assert "io_service_batches_total" in text
    assert 'path="local"' in text
