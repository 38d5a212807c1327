"""Dynamic race sanitizer: unit contracts + threaded integration.

Unit half: the :class:`LockRegistry` reports unguarded writes, detects
lock-order cycles, tolerates RLock re-entrancy, and backs a
``threading.Condition`` (the admission queue's ``_wake`` shape).

Integration half (the ISSUE's satellite): the real threaded paths —
admission worker + concurrent submitters, the ingest pump, the chaos
proxy's injected-failure counter, concurrent CMDB registration — run
instrumented and must produce **zero** unguarded writes and **zero**
lock-order cycles (the ``racecheck`` fixture fails the test otherwise).
"""
import threading
import time

import numpy as np
import pytest

from repro.analysis.racecheck import (LockRegistry,
                                      instrument_admission_queue,
                                      instrument_cmdb,
                                      instrument_fault_server,
                                      instrument_pump, instrument_server)
from repro.core import EngineConfig, ResourceRequest
from repro.core.types import Recommendation
from repro.operator.chaos import FaultInjectedServer
from repro.operator.cmdb import PoolCMDB
from repro.serve import BatchServer, DeviceArchive
from repro.stream import AdmissionQueue, IngestPump

from test_serve_batch import synth_candidates


class Counter:
    def __init__(self):
        self.n = 0


# ---------------------------------------------------------------------------
# registry unit contracts
# ---------------------------------------------------------------------------

def test_unguarded_write_is_reported():
    reg = LockRegistry()
    try:
        lock = reg.wrap(threading.Lock(), "c.lock")
        c = Counter()
        reg.guard(c, fields=("n",), locks=("c.lock",), label="Counter")
        with lock:
            c.n += 1                      # under the mapped lock: clean
        assert reg.race_reports() == []
        c.n += 1                          # off-lock: one report
        (rep,) = reg.race_reports()
        assert rep.obj == "Counter" and rep.attr == "n"
        assert "unguarded write" in rep.format()
        assert reg.problems() and c.n == 2    # the write still lands
        with pytest.raises(AssertionError, match="racecheck"):
            reg.assert_clean()
    finally:
        reg.close()


def test_lock_order_cycle_detected():
    reg = LockRegistry()
    a = reg.wrap(threading.Lock(), "A")
    b = reg.wrap(threading.Lock(), "B")
    with a:
        with b:
            pass
    with b:
        with a:                           # inverted order: A->B and B->A
            pass
    (cycle,) = reg.cycles()
    assert set(cycle) == {"A", "B"}
    assert any("deadlock" in p for p in reg.problems())


def test_consistent_lock_order_is_clean():
    reg = LockRegistry()
    a = reg.wrap(threading.Lock(), "A")
    b = reg.wrap(threading.Lock(), "B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert reg.edges() == [("A", "B")]
    assert reg.cycles() == [] and reg.problems() == []


def test_rlock_reentrancy_orders_nothing():
    reg = LockRegistry()
    r = reg.wrap(threading.RLock(), "R")
    with r:
        with r:
            assert reg.held_now() == ("R", "R")
    assert reg.held_now() == ()
    assert reg.edges() == [] and reg.problems() == []


def test_condition_over_instrumented_lock():
    # the admission queue's _wake shape: Condition sharing the queue lock
    reg = LockRegistry()
    lock = reg.wrap(threading.Lock(), "q.lock")
    cond = threading.Condition(lock)
    box = []

    def waiter():
        with cond:
            while not box:
                if not cond.wait(timeout=10.0):
                    return
            box.append("woke")

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    with cond:
        box.append("signal")
        cond.notify()
    t.join(10.0)
    assert not t.is_alive() and "woke" in box
    assert reg.problems() == []


def test_close_restores_setattr():
    reg = LockRegistry()
    c = Counter()
    orig = type(c).__setattr__
    reg.guard(c, fields=("n",), locks=("never-held",))
    assert type(c).__setattr__ is not orig
    reg.close()
    c.n += 5                              # unpatched again: no report
    assert reg.race_reports() == []


# ---------------------------------------------------------------------------
# threaded integration over the real objects
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cands():
    return synth_candidates(seed=11, K=32)


def test_threaded_admission_serving_is_race_free(racecheck, cands):
    server = BatchServer(bucket_sizes=(1, 4, 16), config=EngineConfig())
    q = AdmissionQueue(server, DeviceArchive.stage(cands),
                       max_wait_s=0.01, max_pending=64)
    instrument_server(racecheck, server)
    instrument_admission_queue(racecheck, q)
    q.start()
    try:
        def client(i):
            for j in range(5):
                t = q.submit(ResourceRequest(cpus=float(8 * (1 + (i + j) % 4))))
                t.result(timeout=60.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120.0)
        assert not any(t.is_alive() for t in threads)
    finally:
        q.stop()
    assert q.stats.submitted == 20 and q.stats.served == 20
    assert server.stats.requests == 20
    assert server.stats.d2h_bytes > 0
    assert racecheck.problems() == []     # fixture re-checks at teardown


def test_ingest_pump_is_race_free(racecheck):
    from test_stream import _pump_world
    _, _, ing, collect = _pump_world()
    pump = IngestPump(ing, collect)
    instrument_pump(racecheck, pump)
    with pump:
        deadline = time.monotonic() + 30.0
        while pump.ticks_pumped < 3 and time.monotonic() < deadline:
            time.sleep(0.01)
    assert pump.ticks_pumped >= 3 and pump.errors == 0
    assert racecheck.problems() == []


def test_fault_injected_counter_is_race_free(racecheck):
    fs = FaultInjectedServer(object())    # armed path never touches it
    instrument_fault_server(racecheck, fs)
    fs.armed = True
    hits = []

    def hammer():
        got = 0
        for _ in range(25):
            try:
                fs.serve(None, [])
            except RuntimeError:
                got += 1
        hits.append(got)

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert sum(hits) == 100 and fs.injected_failures == 100
    assert racecheck.problems() == []


class _FakeItem:
    vcpus = 8.0
    memory_gb = 64.0


class _FakeCatalog:
    def get(self, name):
        return _FakeItem()


def _rec():
    one = np.asarray([1.0])
    return Recommendation(
        names=np.asarray(["m5.2xlarge"]), regions=np.asarray(["us-east-1"]),
        azs=np.asarray(["a"]), counts=one, combined=one,
        availability=np.asarray([90.0]), cost=one, hourly_cost=0.5)


def test_cmdb_concurrent_registration_is_race_free(racecheck):
    cmdb = PoolCMDB(_FakeCatalog())
    instrument_cmdb(racecheck, cmdb)

    def register(i):
        for j in range(10):
            cmdb.record_issued(ResourceRequest(cpus=float(8 * (i * 10 + j))),
                               _rec(), now=float(j))

    threads = [threading.Thread(target=register, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert len(cmdb) == 40                # every distinct signature tracked
    assert cmdb.n_interruptions() == 0
    assert racecheck.problems() == []
