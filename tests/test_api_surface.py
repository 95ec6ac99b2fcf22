"""Public-API snapshot: ``repro.core.__all__`` is a compatibility contract.

Old names must keep resolving (the positional spelling is the documented
compatibility form) and the codelet-frontend surface must stay exported.
Update the snapshot deliberately when the API grows — never by accident.
"""
import subprocess
import sys
from pathlib import Path

import pytest

import repro.core as core

# The quickstart subprocess compiles both backends and allows itself 300s;
# the pytest-timeout cap must sit above that.
pytestmark = pytest.mark.timeout(360)

# frozen snapshot — PR 4 (codelet frontend) state
EXPECTED = sorted([
    # access modes / data
    "AccessMode", "SpAccess", "SpArrayAccess", "SpAtomicWrite",
    "SpAtomicWriteArray", "SpCommutativeWrite", "SpCommutativeWriteArray",
    "SpData", "SpMaybeWrite", "SpMaybeWriteArray", "SpPriority", "SpRead",
    "SpReadArray", "SpWrite", "SpWriteArray", "SpWriteRef",
    # impl variants
    "SpCpu", "SpCuda", "SpHip", "SpHost", "SpImpl", "SpPallas", "SpRef",
    # comm (PR 5: transport split + wire codec; PR 6: failure detection)
    "ChannelHub", "SocketTransport", "SpTransport", "SpCommGroup",
    "SpCommError", "SpCommTimeoutError", "SpCommAbortedError",
    "SpCommTransientError", "SpRankDeadError",
    "SpDeserializer", "SpSerializer", "decode_message", "default_hub",
    "encode_message", "register_wire_type", "reset_default_hub",
    "mpi_broadcast", "mpi_recv", "mpi_send",
    # engine / graph / runtime
    "SpComputeEngine", "SpWorker", "SpWorkerTeam", "SpWorkerTeamBuilder",
    "SpRuntime", "SpSpeculativeModel", "SpTaskGraph",
    # codelet frontend (PR 4)
    "SpCodelet", "SpSlot", "sp_task", "graph_scope", "current_graph",
    # schedulers
    "CriticalPathScheduler", "FifoScheduler", "LifoScheduler",
    "PriorityScheduler", "SpAbstractScheduler", "WorkStealingScheduler",
    "compute_upward_ranks", "make_scheduler",
    # staged backend + introspection
    "execute_staged", "linearize", "schedule_summary", "trace_metrics",
    # task internals
    "Task", "TaskState", "TaskView",
    # robustness (ISSUE 8): policies, watchdog timeout, elastic runtime
    "ElasticEvent", "SpTaskPolicy", "SpTaskTimeoutError",
])


def test_public_api_snapshot():
    assert sorted(core.__all__) == EXPECTED


def test_all_names_resolve():
    missing = [n for n in core.__all__ if not hasattr(core, n)]
    assert not missing, f"__all__ names that do not resolve: {missing}"


def test_quickstart_example_runs():
    """The quickstart is the documented tour of the frontend; it must run
    (also exercised as a CI smoke step)."""
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "examples" / "quickstart.py")],
        capture_output=True,
        text=True,
        timeout=300,
        env={
            **__import__("os").environ,
            "PYTHONPATH": str(repo / "src"),
            "JAX_PLATFORMS": "cpu",  # a CPU smoke: never inherit a platform
        },
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "staged b =" in proc.stdout  # both backends actually ran
