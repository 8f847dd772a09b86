"""Tier-1 chaos smoke: every cluster recovery + spooled-exchange path
under seeded failpoints, with row-exact parity against the fault-free
run.

Thin pytest wrapper over tools/chaos_smoke.py (also runnable directly
from the CLI) — an elastic discovery-fed in-process cluster survives
one injected task failure, one exchange drop, one 30s straggler
(speculative win), a worker death, a worker killed AFTER spooling its
output (replayed, NOT re-run), an on-disk spool-page corruption
(checksum -> retry from upstream), a fresh worker joining mid-query
(re-created tasks land on it), and a mid-read drain (the worker exits
within its grace; the consumer finishes from the spool);
``retry_policy=NONE`` still fails fast. Recovery is asserted
observable through ``system.runtime.metrics`` and the query-history
``retries`` column inside the tool itself, and the spool directory
must end the run with zero orphaned per-query directories."""
import os
import sys

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))


def test_chaos_smoke():
    import chaos_smoke
    summary = chaos_smoke.run_chaos(sf=0.01)
    assert summary["ok"] is True
    scenarios = summary["scenarios"]
    assert scenarios["task_failure"]["task_retries"] >= 1
    assert scenarios["exchange_drop"]["task_retries"] >= 1
    assert scenarios["straggler"]["speculative_won"] >= 1
    assert scenarios["worker_death"]["task_retries"] >= 1
    assert "retry_none" in scenarios
    # spooled exchange + elastic membership (ISSUE 10)
    assert scenarios["spool_replay"]["spool_replays"] >= 1
    assert scenarios["spool_replay"]["spool_fallbacks"] >= 1
    assert scenarios["spool_corrupt"]["corruptions"] >= 1
    assert scenarios["spool_corrupt"]["task_retries"] >= 1
    assert scenarios["worker_join"]["landed_on_joiner"] >= 1
    assert scenarios["drain_exit"]["task_retries"] == 0
    assert scenarios["drain_exit"]["spool_fallbacks"] >= 1
    assert summary["elastic"]["value"] > 0


def test_fleet_coordinator_kill():
    """ISSUE 19: kill 1 of 3 coordinators mid-run over one shared
    worker pool — zero failed queries (FleetClient re-dispatches),
    survivors drop the dead coordinator's federated resource-group
    counts after the staleness grace, and the loss is observable as
    ``coordinator_lost_total`` through plain SQL."""
    import chaos_smoke
    summary = chaos_smoke.run_fleet_chaos(sf=0.01)
    assert summary["ok"] is True
    kill = summary["scenarios"]["coordinator_kill"]
    assert kill["failed"] == 0
    assert kill["queries"] >= 6
    assert kill["failovers"] >= 1
    assert kill["coordinator_lost_total"] >= 1.0
    assert kill["survivor_lost_view"] == ["coord-2"]


def test_lock_discipline_clean_after_chaos():
    """After the full chaos run (retries, speculation, drain, worker
    death) the runtime lock-order validator saw every engine lock edge
    the cluster plane takes under stress: the acquisition graph must be
    acyclic and no dispatch may have run under a lock."""
    from presto_tpu._devtools import lockcheck
    assert lockcheck.ENABLED
    assert lockcheck.GRAPH.check() == [], lockcheck.GRAPH.check()


def test_chaos_spec_with_unknown_site_fails_fast():
    """A typo'd chaos spec must raise at parse time — a config that
    injects nothing would 'pass' every recovery scenario it was meant
    to exercise."""
    import pytest
    from presto_tpu.exec.failpoints import FAILPOINTS
    with pytest.raises(ValueError, match="unknown failpoint site"):
        FAILPOINTS.configure_from_spec("worker.task_ruin=error")
