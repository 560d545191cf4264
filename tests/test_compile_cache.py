"""Persistent compilation cache + warm fleet restarts (ISSUE 7):
compile_cache configuration/manifests, the engine's 3-way
memory_hit/persistent_hit/miss split, cross-PROCESS cache-key
stability (subprocess golden: the second process serving the same
model/bucket records persistent_hit where the first recorded miss),
the watchdog's first-visit-compile tolerance, and the 2-engine
rolling-restart drill (zero request loss through failover, warm
replacement replays the router's fleet manifest)."""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (backend init before serving)
from mxnet_tpu import compile_cache, nd
from mxnet_tpu.serving import ServingEngine, ServingRouter
from mxnet_tpu.telemetry import events
from mxnet_tpu.telemetry import recorder as flight
from test_selfheal import _wait

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class StubModel:
    def __init__(self, delay=0.0):
        self.delay = delay
        self.shapes = []

    def __call__(self, ids, token_types, valid_length, segment_ids,
                 positions):
        if self.delay:
            time.sleep(self.delay)
        self.shapes.append(tuple(ids.shape))
        return nd.array(ids.asnumpy().astype(np.float32)[..., None])


# ---------------------------------------------------------------------------
# module units
# ---------------------------------------------------------------------------

@pytest.fixture
def restore_cache_config():
    """configure(force=True) re-points a process-wide jax setting:
    put the session's own back afterwards."""
    import jax

    saved = dict(compile_cache._state)
    saved_dir = jax.config.jax_compilation_cache_dir
    yield
    compile_cache._state.update(saved)
    jax.config.update("jax_compilation_cache_dir", saved_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      saved["min_s"] if saved["min_s"] is not None else 1.0)


def test_configure_respects_env_knobs(tmp_path, monkeypatch,
                                      restore_cache_config):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "cc"))
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE_MIN_S", "0.25")
    st = compile_cache.configure(force=True)
    assert st["configured"]
    assert st["dir"] == str(tmp_path / "cc")
    assert st["min_s"] == 0.25
    assert os.path.isdir(st["dir"])
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "cc")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.25
    # idempotent: a plain call re-reads nothing
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "cc2"))
    assert compile_cache.configure()["dir"] == str(tmp_path / "cc")
    assert compile_cache.configure(force=True)["dir"] \
        == str(tmp_path / "cc2")


def test_configure_yields_to_jax_own_env_var(tmp_path, monkeypatch,
                                             restore_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: the cache lives there and NO code
    path sets jax_compilation_cache_dir (the environment placed it)."""
    import jax

    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (updates.append(name), real_update(name, val))[1])
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outer"))
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE_DIR",
                       str(tmp_path / "ignored"))
    st = compile_cache.configure(force=True)
    assert st["configured"] and st["dir"] == str(tmp_path / "outer")
    assert "jax_compilation_cache_dir" not in updates
    assert not (tmp_path / "ignored").exists()


def test_default_cache_dir_is_fixed_in_the_checkout(tmp_path):
    """Nothing set: a fresh process, whatever its $HOME and cwd, keeps
    the cache in <checkout>/.jax_cache — never $HOME, a temp name, a
    pid or a time (a cache that moves never hits)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               PYTHONPATH=ROOT)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("MXNET_TPU_COMPILE_CACHE_DIR", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; from mxnet_tpu import compile_cache as cc; "
         "print(cc.configure()['dir']); "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, timeout=120, env=env,
        cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [os.path.join(ROOT, ".jax_cache")] * 2
    assert not list(tmp_path.iterdir())


def test_configure_gate_off(monkeypatch):
    monkeypatch.setenv("MXNET_TPU_COMPILE_CACHE", "0")
    saved = dict(compile_cache._state)
    try:
        compile_cache._state.update(configured=False, dir=None,
                                    min_s=None)
        st = compile_cache.configure()
        assert not st["configured"]
        assert not compile_cache.enabled()
    finally:
        compile_cache._state.update(saved)


def test_classify_and_snapshot_delta():
    a = {"persistent_hits": 3, "persistent_misses": 5}
    hit = {"persistent_hits": 5, "persistent_misses": 5}
    fresh = {"persistent_hits": 5, "persistent_misses": 6}
    idle = {"persistent_hits": 3, "persistent_misses": 5}
    assert compile_cache.classify(a, hit) == "persistent_hit"
    assert compile_cache.classify(a, fresh) == "miss"
    # no compile at all (pure in-memory replay) is not a disk hit
    assert compile_cache.classify(a, idle) == "miss"


def test_manifest_merge_save_load_roundtrip(tmp_path):
    m0 = compile_cache.new_manifest("e0", (64, 256), 8,
                                    [(1, 64), (2, 64)])
    m1 = compile_cache.new_manifest("e1", (64,), 4, [(4, 64)])
    merged = compile_cache.merge_manifests([m0, None, m1])
    assert merged["engines"] == ["e0", "e1"]
    assert merged["bucket_lens"] == [64, 256]
    assert merged["max_rows"] == 8
    assert compile_cache.manifest_shapes(merged) == \
        [(1, 64), (2, 64), (4, 64)]
    path = compile_cache.save_manifest(merged,
                                       str(tmp_path / "m" / "fleet.json"))
    loaded = compile_cache.load_manifest(path)
    assert compile_cache.manifest_shapes(loaded) == \
        compile_cache.manifest_shapes(merged)
    # malformed file degrades to None, not a crash
    with open(path, "w") as f:
        f.write("{not json")
    assert compile_cache.load_manifest(path) is None
    assert compile_cache.load_manifest(str(tmp_path / "absent")) is None
    assert compile_cache.merge_manifests([None, None]) is None
    assert compile_cache.manifest_shapes({"shapes": "bogus"}) == []
    # a structurally malformed part (version-skewed remote) is
    # skipped, never raised — the valid parts still merge
    broken = {"engines": ["ev"], "bucket_lens": ["x"],
              "shapes": ["not-a-pair"], "max_rows": "?"}
    merged2 = compile_cache.merge_manifests([broken, m0])
    assert compile_cache.manifest_shapes(merged2) == [(1, 64), (2, 64)]


# ---------------------------------------------------------------------------
# manifest round-trip: engine export -> router collect/persist -> replay
# ---------------------------------------------------------------------------

def test_manifest_roundtrip_engine_router_replay(tmp_path, monkeypatch):
    manifest_file = str(tmp_path / "fleet_manifest.json")
    monkeypatch.setenv("MXNET_TPU_WARMUP_MANIFEST", manifest_file)
    e0 = ServingEngine(StubModel(), bucket_lens=(8, 16), max_rows=2,
                       engine_id="mr-e0").start()
    router = ServingRouter(engines=[e0], poll_interval_s=0.05).start()
    try:
        for toks in ([1, 2, 3], list(range(12)), [5] * 10):
            router.submit(toks).result(timeout=30)
        visited = set(compile_cache.manifest_shapes(e0.warmup_manifest()))
        assert visited                      # at least one bucket seen
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            persisted = compile_cache.load_manifest(manifest_file)
            if persisted and set(compile_cache.manifest_shapes(
                    persisted)) == visited:
                break
            time.sleep(0.05)
        assert persisted, "router never persisted the fleet manifest"
        assert set(compile_cache.manifest_shapes(persisted)) == visited
        assert persisted["engines"] == ["mr-e0"]
        assert router.snapshot()["manifest_shapes"] == len(visited)
    finally:
        router.stop()
        e0.stop()

    # a fresh engine replays EXACTLY the persisted manifest (not the
    # whole universe), straight from the file path
    stub = StubModel()
    e1 = ServingEngine(stub, bucket_lens=(8, 16), max_rows=2,
                       engine_id="mr-e1").start()
    try:
        e1.warmup(manifest=manifest_file)
        assert set(stub.shapes) == visited
        assert set(compile_cache.manifest_shapes(
            e1.warmup_manifest())) == visited
    finally:
        e1.stop()

    # incompatible bucket config: every manifest shape is skipped
    stub2 = StubModel()
    e2 = ServingEngine(stub2, bucket_lens=(64,), max_rows=1,
                       engine_id="mr-e2").start()
    try:
        e2.warmup(manifest=compile_cache.load_manifest(manifest_file))
        assert stub2.shapes == []
    finally:
        e2.stop()


def test_engine_snapshot_and_healthz_carry_cache_fields():
    eng = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=1,
                        engine_id="snap-e")
    with eng:
        eng.infer([1, 2], timeout=30)
        eng.infer([3, 4], timeout=30)
        snap = eng.snapshot()
        assert snap["compile_cache"]["memory_hit"] == 1
        assert (snap["compile_cache"]["miss"]
                + snap["compile_cache"]["persistent_hit"]) == 1
        assert snap["manifest_shapes"] == 1
        assert snap["compiling"] is False
        srv = eng.expose()
        import urllib.request
        hz = json.loads(urllib.request.urlopen(
            srv.url("/healthz"), timeout=10).read())
        assert hz["compiling"] is False
        man = json.loads(urllib.request.urlopen(
            srv.url("/warmup"), timeout=10).read())
        assert compile_cache.manifest_shapes(man) == [(1, 16)]


# ---------------------------------------------------------------------------
# cross-process golden: the cache key survives a process restart
# ---------------------------------------------------------------------------

def _run_golden_worker(cache_dir, dir_var="MXNET_TPU_COMPILE_CACHE_DIR"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXNET_TPU_COMPILE_CACHE_MIN_S="0",
               MXNET_TPU_WATCHDOG="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("MXNET_TPU_COMPILE_CACHE_DIR", None)
    env[dir_var] = str(cache_dir)
    out = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "tests", "compile_cache_worker.py")],
        capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "dir_var", ["MXNET_TPU_COMPILE_CACHE_DIR", "JAX_COMPILATION_CACHE_DIR"])
def test_cross_process_persistent_hit_golden(tmp_path, dir_var):
    """THE acceptance golden: process 1 cold-compiles (miss), process
    2 — same model, same bucket, same cache dir — serves off the disk
    cache and records persistent_hit without a fresh backend compile.
    Placed by either variable, the entries land in the named dir."""
    cache_dir = tmp_path / "shared_cache"
    first = _run_golden_worker(cache_dir, dir_var)
    assert first["compile_cache"]["miss"] >= 1
    assert first["compile_cache"]["persistent_hit"] == 0
    assert first["state"]["dir"] == str(cache_dir)
    assert os.listdir(cache_dir), "nothing persisted to the cache dir"

    second = _run_golden_worker(cache_dir, dir_var)
    assert second["compile_cache"]["persistent_hit"] >= 1
    assert second["compile_cache"]["miss"] == 0, \
        "second process recompiled despite the primed persistent cache"
    assert second["jax_events"]["persistent_hits"] >= 1


# ---------------------------------------------------------------------------
# watchdog compile tolerance (ROADMAP carried follow-up)
# ---------------------------------------------------------------------------

def test_watchdog_tolerates_first_visit_compile_but_trips_on_stall(
        tmp_path, monkeypatch):
    """A first-visit 'compile' longer than the stall threshold must
    NOT trip the serving-stall probe (the compile window widens it);
    a genuine stall on an already-compiled shape still must.

    Nothing is read after a fixed time: the compile is several stall
    thresholds long, the quiet stretch after it is counted in watchdog
    polls, and the stall's event and bundle are each waited for. (Under
    six xdist workers the bundle used to be listed while the watchdog
    thread was still writing it.)"""
    monkeypatch.setenv("MXNET_TPU_FLIGHT_DIR", str(tmp_path / "flight"))
    events.configure(str(tmp_path / "wd.jsonl"))
    saved = flight.configure()
    stall_s = 1.0       # a starved worker thread must not look wedged
    flight.configure(interval_s=0.05, stall_s=stall_s,
                     min_dump_interval_s=0.0)
    gate = threading.Event()
    polls = []
    flight.register_probe("poll_counter", lambda: polls.append(1))

    class CompileThenStall:
        """1st call per shape: slow (a compile). Later calls: instant,
        except the 3rd overall which blocks — a wedged forward."""

        def __init__(self):
            self.calls = 0

        def __call__(self, ids, token_types, valid_length, segment_ids,
                     positions):
            self.calls += 1
            if self.calls == 1:
                time.sleep(3 * stall_s)     # compile >> stall_s
            elif self.calls >= 3:
                gate.wait(30)               # genuine stall
            return nd.array(ids.asnumpy().astype(np.float32)[..., None])

    def stall_trips():
        return [t for t in events.read_events(log_path,
                                              event="watchdog_anomaly")
                if t.get("kind") == "serving_worker_stall"]

    eng = ServingEngine(CompileThenStall(), bucket_lens=(16,),
                        max_rows=1)
    fut = None
    try:
        eng.start()
        eng.infer([1, 2, 3], timeout=30)    # slow first-visit compile
        log_path = events.get_log().path
        seen = len(polls)
        _wait(lambda: len(polls) >= seen + 5, what="five watchdog polls")
        stalls = stall_trips()
        assert not stalls, f"compile window tripped the watchdog: {stalls}"
        compiles = events.read_events(log_path, event="compile_end")
        assert compiles and compiles[0]["result"] in ("miss",
                                                      "persistent_hit")

        eng.infer([4, 5], timeout=30)       # memory_hit, fast
        fut = eng.submit([6, 7, 8])         # 3rd call: wedges
        _wait(stall_trips, what="the genuine stall to trip the watchdog")
        # the compile window produced no bundle; the stall did (the
        # event is emitted before the bundle is renamed into place)
        root = str(tmp_path / "flight")
        _wait(lambda: os.path.isdir(root) and any(
            "serving_worker_stall" in d and not d.endswith(".tmp")
            for d in os.listdir(root)), what="the stall's flight bundle")
    finally:
        gate.set()
        if fut is not None:
            try:
                fut.result(timeout=30)
            except Exception:
                pass
        eng.stop()
        flight.unregister_probe("poll_counter")
        events.configure(None)
        flight.configure(**saved)


def test_router_poll_does_not_mark_compiling_engine_down():
    """The router's wedge detection (stale beat + queued work) must
    exempt an engine whose healthz reports an open compile window —
    but only within the SAME finite grace as the engine watchdog: a
    compile outliving stall+grace is a wedge."""
    eng = ServingEngine(StubModel(), bucket_lens=(16,), max_rows=1,
                        engine_id="cw-e0")
    router = ServingRouter(engines=[eng], poll_interval_s=60.0,
                           health_fail_after=1)
    with eng:
        router.start()
        try:
            seat = router._seats["cw-e0"]
            # beat age above the stall threshold (30 s default) but
            # inside stall+grace (330 s default)
            snap = {"running": True, "queue_depth": 3,
                    "seconds_since_beat": 100.0, "compiling": True,
                    "manifest_shapes": 0, "counters": {}}
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(seat, "health",
                           lambda: (True, dict(snap)))
                router._poll_once()
                assert seat.routable        # compiling: exempt
                mp.setattr(seat, "health", lambda: (
                    True, dict(snap, seconds_since_beat=10_000.0)))
                router._poll_once()         # compile outlived grace
                assert not seat.routable
        finally:
            router.stop()


# ---------------------------------------------------------------------------
# rolling-restart drill (in-process, 2 engines)
# ---------------------------------------------------------------------------

def test_restart_drill_zero_loss_and_warm_replay(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_TPU_WARMUP_MANIFEST",
                       str(tmp_path / "drill_manifest.json"))
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    from serve_loadgen import run_load

    e0 = ServingEngine(StubModel(delay=0.02), bucket_lens=(8, 16),
                       max_rows=2, engine_id="rd-e0").start()
    e1 = ServingEngine(StubModel(delay=0.02), bucket_lens=(8, 16),
                       max_rows=2, engine_id="rd-e1").start()
    router = ServingRouter(engines=[e0, e1],
                           poll_interval_s=0.05).start()
    clients, reqs = 4, 24
    total = clients * reqs
    replacement = []
    drill_err = []

    def controller():
        try:
            while router.count("completed") < total // 6:
                time.sleep(0.01)
            # kill only while the victim actually has work IN FLIGHT:
            # the drill must always exercise the failover-requeue path
            # (a lucky kill between dispatches would count 0 failovers)
            deadline = time.monotonic() + 10.0
            while time.monotonic() < deadline:
                row = router.scoreboard().get("rd-e1") or {}
                if row.get("outstanding", 0) > 0:
                    break
                time.sleep(0.002)
            e1.stop(drain=False)
            router.remove_engine("rd-e1")
            stub = StubModel(delay=0.02)
            fresh = ServingEngine(stub, bucket_lens=(8, 16), max_rows=2,
                                  engine_id="rd-e1").start()
            manifest = router.warmup_manifest()
            fresh.warmup(manifest=manifest)
            replacement.append((fresh, stub, manifest))
            router.add_engine("rd-e1", fresh)
        except Exception as e:
            drill_err.append(e)

    ctl = threading.Thread(target=controller, daemon=True,
                           name="test_restart_controller")
    try:
        ctl.start()
        report = run_load(router, n_clients=clients,
                          requests_per_client=reqs, min_len=4,
                          max_len=16, vocab=100)
        ctl.join(timeout=60)
        assert not drill_err, drill_err
        # ZERO LOSS: the kill translated into failover requeues, every
        # submitted request completed, none errored
        assert report["completed"] == total, report
        assert report["errors"] == 0 and report["shed"] == 0, report
        assert report["failovers"] >= 1
        # the loadgen observed the restart and timed first service
        restarts = report.get("restarts")
        assert restarts and restarts[0]["engine_id"] == "rd-e1", report
        assert restarts[0]["ttft_ms"] is not None
        # warm replacement replayed the manifest it was handed (the
        # fleet manifest may GROW afterwards as traffic continues)
        fresh, stub, manifest = replacement[0]
        replayed = set(stub.shapes[:len(
            compile_cache.manifest_shapes(manifest))])
        assert replayed == set(compile_cache.manifest_shapes(manifest))
    finally:
        router.stop()
        e0.stop()
        for eng, *_ in replacement:
            eng.stop()


def test_remove_engine_unknown_raises():
    eng = ServingEngine(StubModel(), bucket_lens=(8,), max_rows=1,
                        engine_id="rm-e0")
    router = ServingRouter(engines=[eng])
    with pytest.raises(KeyError):
        router.remove_engine("nope")


# ---------------------------------------------------------------------------
# telemetry_dump split helper
# ---------------------------------------------------------------------------

def test_telemetry_dump_compile_cache_split():
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import telemetry_dump

    text = "\n".join([
        'mxnet_tpu_serving_compile_cache_total{engine_id="a",'
        'result="memory_hit"} 5',
        'mxnet_tpu_serving_compile_cache_total{engine_id="a",'
        'result="persistent_hit"} 2',
        'mxnet_tpu_serving_compile_cache_total{engine_id="b",'
        'result="miss"} 1',
        'mxnet_tpu_compile_cache_persistent_total{result="hit"} 2',
        'mxnet_tpu_compile_cache_persistent_total{result="miss"} 3',
    ]) + "\n"
    split = telemetry_dump.compile_cache_split(text)
    assert split["a"] == {"memory_hit": 5.0, "persistent_hit": 2.0}
    assert split["b"] == {"miss": 1.0}
    assert split["(jax)"] == {"persistent_hit": 2.0,
                              "persistent_miss": 3.0}
