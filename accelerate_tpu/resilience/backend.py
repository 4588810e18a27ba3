"""Pillar 1 — hardened backend init.

A backend that hangs while it initialises cannot be cancelled in-process (a
hung PJRT client holds the C++ runtime lock), so the hardened path asks
first, from outside:

* the probe runs in a THROWAWAY subprocess, which has EXITED before this
  process touches JAX — a chip belongs to one process at a time, so a probe
  still alive would hold the very device the trainer is about to open;
* configurable attempts with exponential backoff + jitter (a backend that is
  coming back is worth a few spaced retries);
* an OPT-IN platform fallback chain.  By default there is none: the
  requested platform comes up or the init fails.  A chain given explicitly
  (``platforms=`` / ``ACCELERATE_RESILIENCE_INIT_FALLBACK``) is tried in
  order, one probe each, and a fallback that comes up is applied — and
  reported as what it is, a failure of the request (``ok=False``,
  ``fallback=<platform>``).  A platform whose own probe failed is never
  applied: nothing is pinned, ``platform`` stays ``None``;
* a structured :class:`InitReport` (per-attempt cause, elapsed, fallback)
  that the resilience hub emits as a telemetry event.

Opt-in at state construction via ``ACCELERATE_RESILIENCE_INIT=1`` (see
``state.PartialState``, which raises when nothing came up), or call
:func:`init_backend` directly.  Env knobs:
``ACCELERATE_RESILIENCE_INIT_ATTEMPTS`` (5),
``ACCELERATE_RESILIENCE_INIT_TIMEOUT_S`` (120),
``ACCELERATE_RESILIENCE_INIT_BACKOFF_S`` (5),
``ACCELERATE_RESILIENCE_INIT_FALLBACK`` (comma chain, default empty).
"""

from __future__ import annotations

import os
import random
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Callable, Optional

# $JAX_PLATFORMS, inherited or pinned for the probe, alone selects the backend
_PROBE_CODE = "import jax; d = jax.devices(); print(d[0].platform, len(d))"

# most recent report from this process — the resilience hub picks it up at
# Accelerator construction so an init that ran before telemetry existed
# still lands in the event stream
LAST_INIT_REPORT: Optional["InitReport"] = None


@dataclass
class InitAttempt:
    platform: str  # "(default)" = whatever the environment selects
    ok: bool
    detail: str
    elapsed_s: float

    def to_dict(self) -> dict:
        return {
            "platform": self.platform,
            "ok": self.ok,
            "detail": self.detail,
            "elapsed_s": round(self.elapsed_s, 3),
        }


@dataclass
class InitReport:
    """Structured outcome of one hardened init: which platform came up, how
    many probes it took, and what each failed attempt saw."""

    requested: str
    platform: Optional[str]  # platform that came up (None = nothing probed ok)
    ok: bool  # the REQUESTED platform came up
    fallback: Optional[str]  # the chain entry that came up instead, if any
    attempts: list[InitAttempt] = field(default_factory=list)
    elapsed_s: float = 0.0
    ts: float = 0.0  # epoch seconds at init start (outage-log joinable)

    @property
    def requested_attempts(self) -> list[InitAttempt]:
        return [a for a in self.attempts if a.platform == self.requested]

    def to_event(self) -> dict:
        return {
            "event": "init",
            "requested": self.requested,
            "platform": self.platform,
            "ok": self.ok,
            "fallback": self.fallback,
            "attempts": len(self.attempts),
            "elapsed_s": round(self.elapsed_s, 3),
            "detail": self.attempts[-1].detail if self.attempts else "",
        }


def probe_backend_once(
    platform: Optional[str] = None,
    timeout_s: float = 120.0,
    injector=None,
) -> tuple[bool, str]:
    """Try initializing a JAX backend in a throwaway subprocess.

    ``platform=None`` probes whatever the current env selects (the requested
    backend); a string pins ``JAX_PLATFORMS`` for the probe only.  Returns
    ``(ok, detail)`` — detail is the probe's stdout on success, the failure
    cause on failure.
    """
    if injector is not None:
        detail = injector.maybe_init_fault(timeout_s)
        if detail is not None:
            return False, detail
    env = os.environ.copy()
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    try:
        proc = subprocess.run(
            [sys.executable, "-c", _PROBE_CODE],
            capture_output=True,
            text=True,
            timeout=timeout_s,
            env=env,
        )
    except subprocess.TimeoutExpired:
        return False, f"backend init exceeded {timeout_s:.0f}s (hung PJRT client)"
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()
        return False, tail[-1][:300] if tail else f"rc={proc.returncode}"
    return True, proc.stdout.strip()


def backoff_delay(
    attempt: int,
    base_s: float,
    cap_s: float = 30.0,
    jitter: float = 0.25,
    rng: Optional[random.Random] = None,
) -> float:
    """One delay of the exponential-backoff schedule: ``base * 2**attempt``,
    capped, with symmetric jitter so a fleet of preempted workers doesn't
    reprobe a recovering backend in lockstep.  The single shared formula —
    init probing and dispatch retry both use it."""
    rng = rng if rng is not None else random.Random()
    delay = min(cap_s, base_s * (2.0 ** attempt))
    return max(0.0, delay * (1.0 + rng.uniform(-jitter, jitter)))


def backoff_delays(
    attempts: int,
    base_s: float,
    cap_s: float = 30.0,
    jitter: float = 0.25,
    rng: Optional[random.Random] = None,
) -> list[float]:
    """Delays BETWEEN ``attempts`` probes (see :func:`backoff_delay`)."""
    rng = rng if rng is not None else random.Random()
    return [
        backoff_delay(attempt, base_s, cap_s, jitter, rng)
        for attempt in range(max(0, attempts - 1))
    ]


def _env_float(name: str, default: float) -> float:
    value = os.environ.get(name)
    return float(value) if value is not None else default


def init_backend(
    platforms: Optional[list[str]] = None,
    attempts: Optional[int] = None,
    timeout_s: Optional[float] = None,
    backoff_s: Optional[float] = None,
    backoff_cap_s: float = 30.0,
    jitter: float = 0.25,
    apply: bool = True,
    telemetry=None,
    injector=None,
    sleep: Callable[[float], None] = time.sleep,
    rng: Optional[random.Random] = None,
) -> InitReport:
    """Probe → retry with backoff → (only if asked) fall down a platform chain.

    ``platforms`` is the ordered chain to try; ``None`` resolves to
    ``[requested] + ACCELERATE_RESILIENCE_INIT_FALLBACK`` — by default just
    ``[requested]``: a run that asked for a TPU and cannot have one fails,
    it does not come up on the CPU and measure that.  The first (requested)
    entry gets the full ``attempts`` budget; each fallback entry gets one
    probe.  ``report.ok`` says the requested platform came up; a fallback
    that came up is named in ``report.fallback`` with ``ok=False``; when no
    probe succeeded ``report.platform`` is ``None`` and nothing is applied.

    With ``apply=True`` a fallback that came up is pinned into
    ``os.environ["JAX_PLATFORMS"]`` for every subprocess, and into
    ``jax.config`` for this one — jax reads the variable when it is
    imported, and it usually has been by now.
    """
    global LAST_INIT_REPORT
    if attempts is None:
        attempts = int(os.environ.get("ACCELERATE_RESILIENCE_INIT_ATTEMPTS", 5))
    if timeout_s is None:
        timeout_s = _env_float("ACCELERATE_RESILIENCE_INIT_TIMEOUT_S", 120.0)
    if backoff_s is None:
        backoff_s = _env_float("ACCELERATE_RESILIENCE_INIT_BACKOFF_S", 5.0)
    if platforms is None:
        requested = os.environ.get("JAX_PLATFORMS") or "(default)"
        chain_env = os.environ.get("ACCELERATE_RESILIENCE_INIT_FALLBACK", "")
        fallbacks = [p.strip() for p in chain_env.split(",") if p.strip()]
        platforms = [requested] + [p for p in fallbacks if p != requested]
    else:
        # an explicit chain defines its own "requested" head
        requested = platforms[0]

    t_start = time.monotonic()
    report = InitReport(
        requested=requested, platform=None, ok=False, fallback=None, ts=time.time()
    )
    for chain_index, platform in enumerate(platforms):
        # full retry budget for the requested platform, one shot per fallback
        budget = max(1, attempts) if chain_index == 0 else 1
        delays = backoff_delays(budget, backoff_s, backoff_cap_s, jitter, rng)
        for attempt in range(budget):
            t0 = time.monotonic()
            ok, detail = probe_backend_once(
                platform=None if platform == "(default)" else platform,
                timeout_s=timeout_s,
                injector=injector,
            )
            report.attempts.append(
                InitAttempt(platform, ok, detail, time.monotonic() - t0)
            )
            if ok:
                report.platform = platform
                break
            if attempt < budget - 1:
                sleep(delays[attempt])
        if report.platform is not None:
            break
    report.ok = report.platform == requested
    if report.platform is not None and not report.ok:
        report.fallback = report.platform
        if apply and report.platform != "(default)":
            os.environ["JAX_PLATFORMS"] = report.platform
            import jax

            try:
                jax.config.update("jax_platforms", report.platform)
            except RuntimeError:  # backend already initialized: env still set
                pass
    report.elapsed_s = time.monotonic() - t_start
    if telemetry is not None and getattr(telemetry, "enabled", False):
        telemetry.record_resilience(report.to_event())
    LAST_INIT_REPORT = report
    return report
