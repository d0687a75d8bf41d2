"""Shared subprocess-with-retry containment for the XLA CPU
rendezvous-deadlock (see test_attention_isolated.py for the full story):
run a collective-heavy workload in its own 2-device child so a SIGABRT
kills a retryable, timeout-capped subprocess instead of the suite."""
import os
import re
import subprocess
import sys
import time

import pytest

ABORT_RCS = (-6, 134)  # SIGABRT raw / via shell
_TIMEOUT_S = 600
#: Total wall-time budget across ALL attempts: a deterministically hanging
#: child must report after ~one timeout's worth of wall clock, not retry
#: 4 x 600 s (ADVICE.md round 5).
_BUDGET_S = 600


def two_device_env(extra=None):
    """A child env pinned to a 2-participant virtual CPU mesh (two
    rendezvous participants on one core collapse the deadlock odds that
    eight have), forced onto the CPU backend."""
    env = dict(os.environ)
    flags = re.sub(
        r"--xla_force_host_platform_device_count=\d+",
        "",
        env.get("XLA_FLAGS", ""),
    )
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=2").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


def run_contained(cmd, env, cwd, retries=3, what="isolated child", budget_s=_BUDGET_S):
    """Run ``cmd`` with retry on the known infra abort (or a hang past the
    timeout, which the XLA collective terminate flag does not always
    cover). A real failure reproduces deterministically in the child and
    fails the calling test with the child's output. Returns the passing
    CompletedProcess.

    Retries share one wall-clock ``budget_s``: each attempt's timeout is the
    time remaining, so a deterministically hanging child reports after
    ~``budget_s`` total instead of ``(1 + retries) * timeout``. Every retry
    is logged to stderr so a flaky-infra loop is visible between attempts."""
    deadline = time.monotonic() + budget_s
    last = None
    for attempt in range(1 + retries):
        remaining = deadline - time.monotonic()
        if attempt > 0 and remaining <= 1.0:
            print(
                f"[{what}] retry budget ({budget_s}s) exhausted after "
                f"{attempt} attempt(s)",
                file=sys.stderr,
                flush=True,
            )
            break
        try:
            last = subprocess.run(
                cmd,
                capture_output=True,
                text=True,
                env=env,
                cwd=cwd,
                timeout=min(_TIMEOUT_S, max(remaining, 1.0)),
            )
        except subprocess.TimeoutExpired as e:
            last = subprocess.CompletedProcess(
                e.cmd,
                -9,
                e.stdout.decode() if isinstance(e.stdout, bytes) else (e.stdout or ""),
                e.stderr.decode() if isinstance(e.stderr, bytes) else (e.stderr or ""),
            )
            print(
                f"[{what}] attempt {attempt + 1}/{1 + retries} timed out, retrying",
                file=sys.stderr,
                flush=True,
            )
            continue  # hang: retry like an abort
        if last.returncode == 0:
            return last
        if last.returncode not in ABORT_RCS:
            break  # a real failure: deterministic, no point retrying
        print(
            f"[{what}] attempt {attempt + 1}/{1 + retries} aborted "
            f"(rc={last.returncode}), retrying",
            file=sys.stderr,
            flush=True,
        )
    pytest.fail(
        f"{what} failed (rc={last.returncode}):\n"
        f"{last.stdout[-4000:]}\n{last.stderr[-2000:]}"
    )
