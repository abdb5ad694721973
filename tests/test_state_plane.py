"""Crash-safe state plane (openr_tpu.state): write-ahead journal +
checkpoint round trips through the PersistentStore, the KvStore merge
hook, the ``state.checkpoint_write`` fault seam, the config store's
no-silent-swallow corruption path, and the watchdog stall counters."""

import os
import time

from openr_tpu.config_store.persistent_store import PersistentStore
from openr_tpu.faults import FaultSchedule, get_injector
from openr_tpu.monitor.watchdog import Watchdog
from openr_tpu.state import LsdbCheckpoint, StatePlane
from openr_tpu.telemetry import get_registry
from openr_tpu.types import KeySetParams, Value
from openr_tpu.utils import wire
from openr_tpu.utils.eventbase import OpenrEventBase


def val(version=1, originator="node-a", value=b"v"):
    return Value(
        version=version,
        originator_id=originator,
        value=value,
        hash=wire.generate_hash(version, originator, value),
    )


def wait_until(pred, timeout=5.0, interval=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


def make_plane(tmp_path, name="state.bin", **kw):
    store = PersistentStore(str(tmp_path / name))
    return store, StatePlane(store, **kw)


class TestStatePlane:
    def test_journal_replay_roundtrip(self, tmp_path):
        store, plane = make_plane(tmp_path)
        plane.on_kvstore_merge("0", {"adj:a": val(1, "a")})
        plane.on_kvstore_merge("0", {"adj:b": val(1, "b")})
        plane.on_kvstore_merge("1", {"adj:c": val(2, "c")})
        # newer version of an earlier key: replay must keep the winner
        plane.on_kvstore_merge("0", {"adj:a": val(3, "a", b"v3")})
        store.stop()

        store2 = PersistentStore(str(tmp_path / "state.bin"))
        rec = StatePlane(store2).recover()
        assert not rec.had_checkpoint
        assert rec.journal_replayed == 4
        assert sorted(rec.key_vals_by_area) == ["0", "1"]
        assert rec.key_vals_by_area["0"]["adj:a"].version == 3
        assert rec.key_vals_by_area["0"]["adj:a"].value == b"v3"
        assert rec.key_vals_by_area["0"]["adj:b"].version == 1
        assert rec.key_vals_by_area["1"]["adj:c"].originator_id == "c"
        store2.stop()

    def test_checkpoint_collapses_journal(self, tmp_path):
        store, plane = make_plane(tmp_path)
        for i in range(5):
            plane.on_kvstore_merge("0", {f"k{i}": val(1, "a")})
        assert plane.journal_length() == 5
        plane.checkpoint()
        assert plane.journal_length() == 0
        # post-checkpoint appends journal again
        plane.on_kvstore_merge("0", {"k9": val(1, "a")})
        assert plane.journal_length() == 1
        store.stop()

        store2 = PersistentStore(str(tmp_path / "state.bin"))
        journal_keys = [
            k for k in store2.keys() if k.startswith("state:lsdb:journal:")
        ]
        assert len(journal_keys) == 1  # pre-checkpoint records erased
        rec = StatePlane(store2).recover()
        assert rec.had_checkpoint
        assert rec.journal_replayed == 1
        assert sorted(rec.key_vals_by_area["0"]) == [
            "k0", "k1", "k2", "k3", "k4", "k9",
        ]
        store2.stop()

    def test_auto_checkpoint_at_threshold(self, tmp_path):
        store, plane = make_plane(tmp_path, checkpoint_every=4)
        for i in range(4):
            plane.on_kvstore_merge("0", {f"k{i}": val(1, "a")})
        # the 4th append crossed the threshold and cut a checkpoint
        assert plane.journal_length() == 0
        assert store.load("state:lsdb:ckpt", LsdbCheckpoint) is not None
        store.stop()

    def test_checkpoint_write_seam_leaves_journal_intact(self, tmp_path):
        reg = get_registry()
        store, plane = make_plane(tmp_path)
        for i in range(3):
            plane.on_kvstore_merge("0", {f"k{i}": val(1, "a")})
        inj = get_injector()
        inj.reset()
        inj.arm("state.checkpoint_write", FaultSchedule.fail_once())
        before = reg.counter_get("state.checkpoint_failures")
        assert plane.maybe_checkpoint() is False
        assert reg.counter_get("state.checkpoint_failures") == before + 1
        # journal untouched: recovery replays everything
        assert plane.journal_length() == 3
        store.stop()
        store2 = PersistentStore(str(tmp_path / "state.bin"))
        rec = StatePlane(store2).recover()
        assert not rec.had_checkpoint
        assert rec.journal_replayed == 3
        assert sorted(rec.key_vals_by_area["0"]) == ["k0", "k1", "k2"]
        store2.stop()
        # the seam self-heals: next attempt commits
        store3, plane3 = make_plane(tmp_path, name="other.bin")
        plane3.on_kvstore_merge("0", {"k": val(1, "a")})
        assert plane3.maybe_checkpoint() is True
        store3.stop()
        inj.reset()

    def test_recovered_plane_continues_journaling(self, tmp_path):
        store, plane = make_plane(tmp_path)
        plane.on_kvstore_merge("0", {"a": val(1, "a")})
        plane.checkpoint()
        plane.on_kvstore_merge("0", {"b": val(1, "b")})
        store.stop()

        store2 = PersistentStore(str(tmp_path / "state.bin"))
        plane2 = StatePlane(store2)
        plane2.recover()
        # seq continues past the crashed process's journal
        plane2.on_kvstore_merge("0", {"c": val(1, "c")})
        store2.stop()

        store3 = PersistentStore(str(tmp_path / "state.bin"))
        rec = StatePlane(store3).recover()
        assert sorted(rec.key_vals_by_area["0"]) == ["a", "b", "c"]
        store3.stop()


class TestKvStoreJournalHook:
    def test_merge_hook_journals_accepted_updates(self, tmp_path):
        from openr_tpu.kvstore.store import KvStore

        store, plane = make_plane(tmp_path)
        kv = KvStore("node-a", areas=["0"], state_plane=plane)
        kv.start()
        try:
            kv.set_key_vals(
                "0", KeySetParams(key_vals={"adj:a": val(1, "a")})
            )
            # a re-merge of the SAME value is a no-op: no journal record
            kv.set_key_vals(
                "0", KeySetParams(key_vals={"adj:a": val(1, "a")})
            )
            kv.set_key_vals(
                "0", KeySetParams(key_vals={"adj:b": val(2, "b")})
            )
            assert wait_until(lambda: plane.journal_length() == 2)
        finally:
            kv.stop()
            store.stop()

        store2 = PersistentStore(str(tmp_path / "state.bin"))
        rec = StatePlane(store2).recover()
        assert sorted(rec.key_vals_by_area["0"]) == ["adj:a", "adj:b"]
        store2.stop()


class TestPersistentStoreCorruption:
    def test_truncated_file_counted_and_kept(self, tmp_path):
        reg = get_registry()
        path = str(tmp_path / "store.bin")
        store = PersistentStore(path)
        store.store("drain-state", {"is_overloaded": True})
        store.store("node-label", 42)
        store.stop()
        with open(path, "rb") as f:
            raw = f.read()
        with open(path, "wb") as f:
            f.write(raw[: len(raw) // 2])

        before = reg.counter_get("config_store.load_errors")
        store2 = PersistentStore(path)
        # no silent swallow: counted, corrupt bytes kept for forensics,
        # store starts empty instead of crashing
        assert reg.counter_get("config_store.load_errors") == before + 1
        assert os.path.exists(path + ".tmp")
        with open(path + ".tmp", "rb") as f:
            assert f.read() == raw[: len(raw) // 2]
        assert store2.load("node-label") is None
        # the store still works: fresh writes land and reload
        store2.store("node-label", 7)
        store2.stop()
        store3 = PersistentStore(path)
        assert store3.load("node-label") == 7
        store3.stop()

    def test_missing_file_is_not_an_error(self, tmp_path):
        reg = get_registry()
        before = reg.counter_get("config_store.load_errors")
        store = PersistentStore(str(tmp_path / "absent.bin"))
        assert store.load("k") is None
        assert reg.counter_get("config_store.load_errors") == before
        store.stop()


class TestWatchdogStallCounters:
    def test_blocked_evb_bumps_stall_counters(self):
        reg = get_registry()
        crashes = []
        wd = Watchdog(
            interval_s=10.0,  # never fires on its own; we drive _check
            thread_timeout_s=0.05,
            crash_handler=crashes.append,
        )
        victim = OpenrEventBase(name="victim")
        victim.run_in_thread()
        victim.wait_until_running()
        healthy = OpenrEventBase(name="healthy")
        healthy.run_in_thread()
        healthy.wait_until_running()
        wd.add_evb("victim", victim)
        wd.add_evb("healthy", healthy)
        try:
            release = __import__("threading").Event()
            victim.run_in_event_base(lambda: release.wait(2.0))
            before = reg.counter_get("watchdog.stalls.victim")
            assert wait_until(
                lambda: time.monotonic() - victim.last_loop_ts > 0.1
            )
            # keep it fresh: an idle loop turns only every 0.1 s, twice
            # the timeout, so without work of its own it reads as stalled
            # whenever this thread is late by a few tens of milliseconds
            healthy.schedule_periodic(0.005, lambda: None)
            assert wait_until(
                lambda: time.monotonic() - healthy.last_loop_ts < 0.02
            )
            wd._check()
            assert reg.counter_get("watchdog.stalls.victim") == before + 1
            assert reg.counter_get("watchdog.stalls.healthy") == 0
            assert reg.snapshot().get("watchdog.stalled") == 1
            assert crashes and "victim" in crashes[0]
            # the gauge clears once the loop unblocks
            release.set()
            assert wait_until(
                lambda: time.monotonic() - victim.last_loop_ts < 0.05
            )
            wd._check()
            assert reg.snapshot().get("watchdog.stalled") == 0
        finally:
            release.set()
            victim.stop()
            victim.join()
            healthy.stop()
            healthy.join()
