#!/usr/bin/env python
"""telemetry_smoke — `make telemetry-smoke`: the one leg of the telemetry
pipeline that needs two real processes.

A REAL 2-rank ``jax.distributed`` gloo/CPU world where rank 1's fault
injector sleeps (``hang:step=2``) before its third ``gather_object``: rank 0
blocks inside the collective, its hang watchdog fires on the stall deadline
and writes ``blackbox_rank0.json``; a SIGTERM to the sleeping rank 1
exercises the watchdog's fatal-signal dump path; then
tools/blackbox_report.py must merge the dumps and name the stalled rank (1)
and the first divergent collective (#3, gather_object).

Everything one process can show is in tests/test_telemetry.py: the watchdog
on a stalled section, the recorder's ring and dumps, the report's merge on
two ranks' dumps, the JSONL schema and the exported trace's three tracks.

Exit 0 = the leg passes.
"""

import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


_HANG_WORKER = textwrap.dedent(
    """
    import json
    import os
    import sys

    pid = int(sys.argv[1])
    port = sys.argv[2]
    blackbox_dir = sys.argv[3]
    out_path = sys.argv[4]

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("XLA_FLAGS", None)  # 1 local device per process
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
    )
    sys.path.insert(0, "@REPO@")

    from accelerate_tpu.resilience.inject import FaultInjector
    from accelerate_tpu.telemetry import flightrec
    from accelerate_tpu.telemetry.watchdog import HangWatchdog
    from accelerate_tpu.utils.operations import gather_object

    # rank 1 goes silent right before the step-2 collective; rank 0 will
    # block inside gather_object #3 until its watchdog deadline fires
    injector = (
        FaultInjector.from_spec("hang:step=2,seconds=600") if pid == 1 else None
    )
    wd = HangWatchdog(timeout_s=3.0, dump_dir=blackbox_dir).start()

    for step in range(4):
        flightrec.record("step_begin", step=step)
        if injector is not None:
            injector.maybe_hang(step)
        gathered = gather_object([step])
        flightrec.record("step_end", step=step)

    # only reached if nothing hung (a failure of this leg)
    wd.stop()
    with open(out_path, "w") as f:
        json.dump({"pid": pid, "completed": True}, f)
    """
).replace("@REPO@", REPO)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _wait_for(path: str, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if os.path.exists(path):
            return True
        time.sleep(0.25)
    return False


def _hang_leg() -> list[str]:
    """Injected hang in a real 2-process world → watchdog dumps → merged
    blackbox report names the stalled rank and collective."""
    from blackbox_report import load_dump, merge

    errors: list[str] = []
    tmp = tempfile.mkdtemp(prefix="atpu_blackbox_")
    blackbox_dir = os.path.join(tmp, "blackbox")
    worker = os.path.join(tmp, "worker.py")
    with open(worker, "w", encoding="utf-8") as f:
        f.write(_HANG_WORKER)
    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(port), blackbox_dir,
             os.path.join(tmp, f"rank{i}.json")],
            env=env, cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        for i in range(2)
    ]
    dump0 = os.path.join(blackbox_dir, "blackbox_rank0.json")
    dump1 = os.path.join(blackbox_dir, "blackbox_rank1.json")
    try:
        # rank 0 blocks in gather #3; its 3s watchdog deadline must produce
        # the stall dump (generous ceiling covers the distributed handshake)
        if not _wait_for(dump0, timeout_s=120):
            errors.append("rank 0 watchdog never dumped on the stall")
        # the hung rank's dump comes from the fatal-signal path: SIGTERM the
        # sleeping rank 1, its watchdog handler dumps then chains to death
        if procs[1].poll() is None:
            procs[1].send_signal(signal.SIGTERM)
        if not _wait_for(dump1, timeout_s=60):
            errors.append("rank 1 watchdog never dumped on SIGTERM")
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for p in procs:
            try:
                p.wait(timeout=30)
            except subprocess.TimeoutExpired:
                errors.append("worker did not die on SIGKILL")
    if errors:
        return errors

    dumps = [d for d in (load_dump(dump0), load_dump(dump1)) if d is not None]
    if len(dumps) != 2:
        return [f"expected 2 parseable dumps, got {len(dumps)}"]
    report = merge(dumps)
    if report["stalled_ranks"] != [1]:
        errors.append(f"stalled rank not identified: {report}")
    if report["first_divergent_seq"] != 3:
        errors.append(f"first divergent collective seq != 3: {report}")
    if report["first_divergent_op"] != "gather_object":
        errors.append(f"divergent op not named: {report}")
    ranks = {r["rank"]: r for r in report["ranks"]}
    if ranks.get(0, {}).get("reason") != "watchdog_stall":
        errors.append(f"rank 0 dump reason != watchdog_stall: {ranks.get(0)}")
    if ranks.get(1, {}).get("reason") != "signal":
        errors.append(f"rank 1 dump reason != signal: {ranks.get(1)}")
    if not ranks.get(1, {}).get("hang_injected"):
        errors.append("rank 1 dump does not show the injected hang")
    if not errors:
        print(
            "telemetry-smoke: hang leg ok — watchdog dumped both ranks, "
            f"report names rank {report['stalled_ranks']} stalled at "
            f"collective #{report['first_divergent_seq']} "
            f"({report['first_divergent_op']})"
        )
    return errors


def main() -> int:
    errors = _hang_leg()
    for error in errors:
        print(f"telemetry-smoke: FAIL: {error}", file=sys.stderr)
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
