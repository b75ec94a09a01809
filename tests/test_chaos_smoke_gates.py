"""tools/chaos_smoke.py: the resilience gates (in-process chaos run,
subprocess hard kill, fleet replica kill, overload + kill)."""
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestChaosSmoke:
    """ISSUE 4 CI satellite: the resilience counters the README
    documents must exist in monitor.snapshot() after a chaos run."""

    def _load(self):
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "chaos_smoke", os.path.join(REPO, "tools", "chaos_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_gate_passes(self):
        # the subprocess hard-kill lane runs as its own gate below, so
        # each test stays within its own time envelope
        assert self._load().main(["--skip-hard-kill"]) == 0

    def test_hard_kill_gate(self):
        # ISSUE 13 acceptance: SIGKILL a subprocess server mid-decode
        # with 4 in-flight requests (greedy + sampled + prefix-hit +
        # draft-opted); the relaunch over the same journal completes
        # all of them bit-identically to an uninterrupted run and
        # /result/<id> re-attaches for every journaled id
        assert self._load().main(["--hard-kill-only"]) == 0

    def test_fleet_kill_gate(self):
        # ISSUE 14 acceptance: SIGKILL one of TWO subprocess replicas
        # mid-decode behind the supervisor + router — every in-flight
        # stream completes bit-exactly on the survivor via
        # journal-backed migration (zero failed requests),
        # fleet_failovers_total / fleet_migrated_requests_total fire,
        # every fleet_*/router_* series exists, and /result/<id>
        # re-attaches through the router for every journaled id
        assert self._load().main(["--fleet-only"]) == 0

    def test_overload_kill_gate(self):
        # ISSUE 19 acceptance: overload AND a replica kill composed —
        # two in-process replicas with SLO budgets + brownout take a
        # decode-delayed batch flood plus interactive traffic, one is
        # hard-killed mid-flood; every interactive request completes,
        # batch arrivals shed with sched_shed_on_arrival_total
        # ticking, failover fires, and every OVERLOAD_SERIES metric
        # (shed counter, brownout gauge, decode preemptions, fleet
        # scale events) exists in monitor.snapshot()
        assert self._load().main(["--overload-only"]) == 0
