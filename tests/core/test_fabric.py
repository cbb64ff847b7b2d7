"""Multi-process shard fabric (PR 6): consistent-hash routing, the WAL
directory lock, digest-verified shard handoff, crash respawn, and the
in-process router mode CI runs the whole suite under."""
import json
import signal
import threading
import time

import pytest

from repro.core import (Client, ClientStudy, DurableStorage, HopaasServer,
                        HttpServiceRunner, HttpTransport, RetryPolicy,
                        ShardFabric, ShardedHttpTransport, TokenManager,
                        WalDirectoryLockedError, suggestions)
from repro.core import fabric as fabric_mod
from repro.core.fabric import HashRing, RouteTable, classify_target
from repro.core.storage import InMemoryStorage

_SPACE = {"x": suggestions.uniform(-1.0, 1.0)}

# generous retry: fabric tests inject crashes/freezes whose recovery
# (respawn ~1.5s) outlasts the default client backoff
_PATIENT = RetryPolicy(max_attempts=8, base_delay=0.1, max_delay=1.0)


def _client(fab, retry=None):
    tok = fab.issue_token("t")
    return Client(HttpTransport(fab.host, fab.port), tok,
                  retry=retry or _PATIENT), tok


def _study(client, name="fab", sampler="random"):
    return ClientStudy(name=name, client=client, properties=dict(_SPACE),
                       sampler={"name": sampler})


# --------------------------------------------------------------------------- #
# consistent-hash ring + request classification
# --------------------------------------------------------------------------- #
def test_hash_ring_minimal_remap_on_grow():
    keys = [f"study-{i:03d}" for i in range(200)]
    r3 = HashRing([0, 1, 2])
    r4 = HashRing([0, 1, 2, 3])
    moved = [k for k in keys if r3.owner(k) != r4.owner(k)]
    # every moved key must move TO the new worker, never between old ones
    assert moved and all(r4.owner(k) == 3 for k in moved)
    # and roughly 1/4 of the keys move, not a full reshuffle
    assert len(moved) < len(keys) // 2
    # placement is deterministic
    assert [r3.owner(k) for k in keys] == [HashRing([2, 1, 0]).owner(k)
                                           for k in keys]


def test_route_table_overrides_and_flip():
    table = RouteTable({0: ("h", 1), 1: ("h", 2)})
    key = "abc123"
    base = table.owner(key)
    other = 1 - base
    table.update(overrides={key: other})
    assert table.owner(key) == other            # override wins over ring
    table.update(clear_overrides=True)
    assert table.owner(key) == base
    # endpoints can grow before the ring flips: reachability before traffic
    table.update(endpoints={0: ("h", 1), 1: ("h", 2), 2: ("h", 3)},
                 ring_ids=[0, 1])
    assert table.endpoint(2) == ("h", 3)
    assert table.worker_ids() == [0, 1]


def test_classify_target_covers_both_surfaces():
    assert classify_target("POST", "/api/v2/studies/k1/trials:ask") == \
        ("key", "k1")
    assert classify_target("POST", "/api/v2/trials/k1:7:tell") == \
        ("key", "k1")
    assert classify_target("POST", "/api/v2/studies") == ("spec",)
    assert classify_target("GET", "/api/v2/studies?limit=5") == ("gather",)
    assert classify_target("POST", "/api/v2/trials:tell_batch") == \
        ("tell_batch",)
    assert classify_target("POST", "/api/ask/TOKEN") == ("spec",)
    assert classify_target("POST", "/api/tell/TOKEN") == ("uid",)
    assert classify_target("POST", "/api/tell_batch/TOKEN") == \
        ("tell_batch",)
    assert classify_target("GET", "/api/studies/TOKEN") == ("gather",)
    assert classify_target("GET", "/api/version") == ("default",)
    assert classify_target("DELETE", "/api/v2/studies") == ("default",)


# --------------------------------------------------------------------------- #
# satellite: exclusive WAL directory lock
# --------------------------------------------------------------------------- #
def test_wal_directory_lock_excludes_second_opener(tmp_path):
    root = str(tmp_path / "store")
    st = DurableStorage(root, fsync="off", auto_compact=False)
    with pytest.raises(WalDirectoryLockedError) as e:
        DurableStorage(root, fsync="off")
    assert "locked by another live process" in str(e.value)
    st.close()                                   # close releases the lock
    st2 = DurableStorage(root, fsync="off")
    st2.close()


# --------------------------------------------------------------------------- #
# fabric end-to-end: routing, both API surfaces, scatter-gather
# --------------------------------------------------------------------------- #
def test_fabric_routes_both_surfaces_and_gathers():
    fab = ShardFabric(workers=2, storage="memory").start()
    try:
        cl, tok = _client(fab)
        studies = [_study(cl, name=f"fab-{i}") for i in range(6)]
        for s in studies:
            s._ensure_key()
        locations = fab.locations()
        owned = {w: len(ks) for w, ks in locations.items()}
        assert sum(owned.values()) == 6
        assert len([w for w, n in owned.items() if n]) >= 1

        # v2 ask/tell through the router proxy
        for s in studies[:3]:
            t = s.ask()
            s.tell(t, value=abs(t.x))
        # v1 surface (spec- and uid-keyed bodies)
        ask = cl._post("ask", studies[0]._spec_body())
        tell = cl._post("tell", {"trial_uid": ask["trial_uid"],
                                 "value": 0.5})
        assert tell["state"] == "completed"

        # tell_batch split by owner, results merged back in order
        trials = [s.ask() for s in studies]
        results = cl.tell_batch(
            [{"trial_uid": t.uid, "value": 0.25, "state": "completed"}
             for t in trials])
        assert [r["uid"] for r in results] == [t.uid for t in trials]
        assert all(r["status"] == 200 for r in results)

        # scatter-gather study lists, v2 (paged) and v1
        v2 = {s["name"] for s in cl.studies()}
        assert {f"fab-{i}" for i in range(6)} <= v2
        status, payload, _ = HttpTransport(fab.host, fab.port).request_full(
            "GET", f"/api/studies/{tok}")
        assert status == 200
        assert {s["name"] for s in payload["studies"]} == v2
        # paging is positional across the merged list
        page = cl.trials_page(studies[0].study_key, limit=1)
        assert len(page["trials"]) == 1
        assert fab.stats()["dispatcher"]["proxied"] > 0
        # every worker's ready line named its sampler device: the CPU,
        # since no fabric child may take the chip by default
        devices = fab.devices()
        assert sorted(devices) == [0, 1]
        assert all(d["platform"] == "cpu" for d in devices.values())
    finally:
        fab.stop()


class _SpawnStopped(Exception):
    pass


@pytest.mark.parametrize("preset,expected", [(None, "cpu"), ("tpu", "tpu")])
def test_spawn_keeps_children_off_the_chip(monkeypatch, preset, expected):
    """Children sample on the CPU unless the operator set JAX_PLATFORMS:
    one chip serves one process."""
    if preset is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", preset)
    seen = {}

    def fake_popen(cmd, stdout, env):
        seen.update(env)
        raise _SpawnStopped

    monkeypatch.setattr(fabric_mod.subprocess, "Popen", fake_popen)
    fab = ShardFabric(workers=2, storage="memory")
    with pytest.raises(_SpawnStopped):
        fab._spawn(0)
    assert seen["JAX_PLATFORMS"] == expected


def test_sharded_transport_skips_the_router_hop():
    fab = ShardFabric(workers=2, storage="memory").start()
    try:
        tok = fab.issue_token("t")
        transport = ShardedHttpTransport(fab.endpoints)
        cl = Client(transport, tok, retry=_PATIENT)
        s = _study(cl, name="direct")
        t = s.ask()
        s.tell(t, value=abs(t.x))
        resource = cl.study(s.study_key)
        assert resource["n_completed"] == 1
        # the keyed requests went straight to the owner: no proxying
        assert fab.stats()["dispatcher"]["proxied"] == 0
        transport.close()
    finally:
        fab.stop()


# --------------------------------------------------------------------------- #
# satellite: kill-and-rebalance a live study mid-campaign
# --------------------------------------------------------------------------- #
def test_migration_digest_identical_zero_lost_tells():
    fab = ShardFabric(workers=2, storage="durable", fsync="off",
                      respawn=False).start()
    try:
        cl, _tok = _client(fab)
        study = _study(cl, name="live")
        key = study._ensure_key()
        src = fab.owner_of(key)
        dst = [w for w in fab.locations() if w != src][0]

        stop = threading.Event()
        told, errors = [], []

        def campaign():
            while not stop.is_set():
                try:
                    t = study.ask()
                    study.tell(t, value=abs(t.x))
                    told.append(t.uid)
                except Exception as e:       # pragma: no cover - the assert
                    errors.append(repr(e))
                    return

        threads = [threading.Thread(target=campaign) for _ in range(3)]
        for th in threads:
            th.start()
        time.sleep(0.3)                      # campaign in full flight
        rec1 = fab.migrate(key, src, dst)    # ...and rebalance under it
        time.sleep(0.2)
        rec2 = fab.migrate(key, dst, src)    # and back
        time.sleep(0.2)
        stop.set()
        for th in threads:
            th.join(timeout=30)
        assert not errors, errors

        # 1) both handoffs were digest-verified index-identical
        assert rec1["verified"] and rec2["verified"]
        assert rec1["src_digest"] == rec1["dst_digest"]
        # 2) zero lost tells: every acknowledged tell is a completion
        resource = cl.study(key)
        completed = {t["uid"] for t in cl.iter_trials(key,
                                                      state="completed")}
        assert set(told) <= completed
        # 3) no double-counted completions
        assert resource["n_completed"] == len(completed)
        assert len(told) == len(set(told))
        # the shard now lives where the second migration put it
        locations = fab.locations()
        assert key in locations[src] and key not in locations[dst]
    finally:
        fab.stop()


def test_add_and_remove_worker_rebalances():
    fab = ShardFabric(workers=2, storage="memory", respawn=False).start()
    try:
        cl, _tok = _client(fab)
        studies = [_study(cl, name=f"grow-{i}") for i in range(8)]
        for s in studies:
            s._ensure_key()
            t = s.ask()
            s.tell(t, value=abs(t.x))
        before = {k for ks in fab.locations().values() for k in ks}

        wid = fab.add_worker()
        locations = fab.locations()
        assert set(locations) == {0, 1, wid}
        assert {k for ks in locations.values() for k in ks} == before
        assert all(h["verified"] for h in fab.handoffs)
        # every study still serves reads and writes after the reshuffle
        for s in studies:
            t = s.ask()
            s.tell(t, value=abs(t.x))
            assert cl.study(s.study_key)["n_completed"] == 2

        fab.remove_worker(wid)
        locations = fab.locations()
        assert set(locations) == {0, 1}
        assert {k for ks in locations.values() for k in ks} == before
        assert cl.study(studies[0].study_key)["n_completed"] == 2
    finally:
        fab.stop()


# --------------------------------------------------------------------------- #
# satellite: a hung worker must not hang the router
# --------------------------------------------------------------------------- #
def test_hung_worker_yields_502_not_a_hung_router():
    fab = ShardFabric(workers=2, storage="memory", upstream_timeout=1.0,
                      respawn=False).start()
    try:
        cl, tok = _client(fab)
        study = _study(cl, name="hang")
        key = study._ensure_key()
        owner = fab.owner_of(key)
        # a study on the *other* worker, created before the wedge
        other = next(s for s in (_study(cl, name=f"hang-{i}")
                                 for i in range(20))
                     if fab.owner_of(s._ensure_key()) != owner)
        fab.kill_worker(owner, sig=signal.SIGSTOP)   # wedge, don't die
        try:
            raw = HttpTransport(fab.host, fab.port, timeout=20.0)
            t0 = time.monotonic()
            status, payload, _ = raw.request_full(
                "POST", f"/api/v2/studies/{key}/trials:ask",
                {"worker_id": "t"},
                headers={"Authorization": f"Bearer {tok}"})
            elapsed = time.monotonic() - t0
            assert status == 502, (status, payload)
            assert payload["error"]["code"] == "bad_upstream"
            # bounded by the 1s upstream timeout, not the 20s client one
            # (generous slack: CI boxes time-share the cores)
            assert elapsed < 10.0
            # other workers' studies keep serving while one is wedged
            t = other.ask()
            other.tell(t, value=0.0)
        finally:
            fab.kill_worker(owner, sig=signal.SIGCONT)
        # the un-wedged worker serves again (client retries ride it out)
        t = study.ask()
        study.tell(t, value=abs(t.x))
    finally:
        fab.stop()


# --------------------------------------------------------------------------- #
# crash respawn: digest-verified recovery + lease requeue
# --------------------------------------------------------------------------- #
def test_crashed_worker_respawns_with_state_and_requeues_leases():
    fab = ShardFabric(workers=2, storage="durable", fsync="always",
                      lease_seconds=1.0, respawn_poll=0.1).start()
    try:
        cl, _tok = _client(fab)
        study = _study(cl, name="crash")
        key = study._ensure_key()
        for _ in range(3):
            t = study.ask()
            study.tell(t, value=abs(t.x))
        leased = study.ask()                 # in flight when the crash hits
        wid = fab.owner_of(key)
        pre_digest = fab.worker_digest(wid)  # latest state, fsynced
        old_pid = fab._workers[wid].pid

        fab.kill_worker(wid, sig=signal.SIGKILL)
        wp = fab.wait_respawn(wid, old_pid)
        assert wp.pid != old_pid
        # recovery replayed the WAL to the exact pre-crash state; under
        # REPRO_REPLICAS>0 the same crash is healed by promoting a
        # follower (failover) instead of respawning on the WAL
        assert wp.digest == pre_digest
        event = [e for e in fab.events
                 if e["event"] in ("respawn", "failover")][-1]
        assert event["digest_match"] is True
        assert event["recovery"]["records_replayed"] >= 0

        # the lease taken through the dead worker lapses and is requeued:
        # the same params come back on the next ask
        time.sleep(1.2)
        revived = study.ask()
        assert revived.params == leased.params
        study.tell(revived, value=abs(revived.params["x"]))
        assert cl.study(key)["n_completed"] == 4
        assert fab.respawns + fab.failovers >= 1
    finally:
        fab.stop()


# --------------------------------------------------------------------------- #
# in-process router mode (REPRO_WORKERS / HttpServiceRunner(workers=N))
# --------------------------------------------------------------------------- #
def test_runner_fabric_mode_preserves_semantics():
    storage = InMemoryStorage()
    tokens = TokenManager()
    servers = [HopaasServer(storage=storage, tokens=tokens, seed=i)
               for i in range(2)]
    # pin the evloop backend: the router needs the dispatcher hook, which
    # the threaded frontend (REPRO_FRONTEND=threaded CI pass) lacks
    runner = HttpServiceRunner(servers, backend="evloop",
                               workers=3).start()
    try:
        cl = Client(HttpTransport(runner.host, runner.port),
                    tokens.issue("t"))
        studies = [_study(cl, name=f"inproc-{i}") for i in range(5)]
        for s in studies:
            t = s.ask()
            s.tell(t, value=abs(t.x))
        assert {s["name"] for s in cl.studies()} >= \
            {f"inproc-{i}" for i in range(5)}
        results = cl.tell_batch(
            [{"trial_uid": s.ask().uid, "value": 0.1, "state": "completed"}
             for s in studies])
        assert all(r["status"] == 200 for r in results)
        stats = runner.frontend_stats()
        assert stats["fabric_workers"] == 3
        assert stats["dispatcher"]["proxied"] > 0
        # the shared storage saw every write exactly once
        assert all(len(list(cl.iter_trials(s.study_key,
                                           state="completed"))) == 2
                   for s in studies)
    finally:
        runner.stop()


def test_runner_threaded_backend_ignores_workers():
    storage = InMemoryStorage()
    tokens = TokenManager()
    runner = HttpServiceRunner(
        [HopaasServer(storage=storage, tokens=tokens)],
        backend="threaded", workers=4)
    assert runner.fabric_workers == 1
    runner.start()
    try:
        cl = Client(HttpTransport(runner.host, runner.port),
                    tokens.issue("t"))
        s = _study(cl, name="threaded")
        t = s.ask()
        s.tell(t, value=0.0)
    finally:
        runner.stop()


def test_fabric_inline_single_worker_matches_plain_service():
    fab = ShardFabric(workers=1, storage="memory").start()
    try:
        assert fab.inline
        cl, _tok = _client(fab)
        s = _study(cl, name="solo")
        t = s.ask()
        s.tell(t, value=abs(t.x))
        assert cl.study(s.study_key)["n_completed"] == 1
        assert fab.stats()["workers"] == 1
        assert "dispatcher" not in fab.stats()
    finally:
        fab.stop()
