#!/usr/bin/env python
"""Run EVERY repo hygiene gate in one command.

The gates existed (``check_atomic_writes.py``,
``check_fast_tier_budget.py``) but nothing tied them together, so a
builder workflow could invoke one and silently drift past the other —
exactly the failure mode gates exist to prevent. This driver is the
single entry point: it runs each gate as a subprocess, prints one
status line per gate, and exits non-zero if ANY gate fails (an
unrunnable gate is a failing gate — silence must never read as
"clean"). It is itself covered by a fast-tier test
(tests/test_gates.py), so the gate list cannot rot unnoticed.

Usage::

    python tools/run_gates.py                     # after the tier-1 run
    python tools/run_gates.py --log /tmp/_t1.log --budget 450
    python tools/run_gates.py --no-budget         # no tier-1 log yet
    python tools/run_gates.py --no-chaos          # skip both chaos smokes
    python tools/run_gates.py --no-serving        # skip engine parity
    python tools/run_gates.py --no-fused          # skip kernel parity
    python tools/run_gates.py --no-observability  # skip the obs smoke

``--no-budget`` skips the fast-tier budget gate for contexts where no
tier-1 log exists (e.g. pre-commit on a docs change); ``--no-chaos``
skips the five chaos smokes (elastic kill-and-resume, serving
overload/poison recovery, fleet replica kill/failover, prefix-cache
shared-page storm, process-worker SIGKILL/SIGSTOP); the atomic-write
gate always runs.

Exit codes: 0 = every gate passed, 1 = at least one gate failed.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys

TOOLS_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(TOOLS_DIR)


def gate_commands(log: str, budget: float, no_budget: bool,
                  no_chaos: bool = False, no_serving: bool = False,
                  no_fused: bool = False,
                  no_observability: bool = False,
                  no_http: bool = False):
    """The authoritative gate list: (name, argv). New hygiene gates
    register HERE (tests/test_gates.py pins the known ones so a gate
    cannot be dropped silently)."""
    gates = [
        ("atomic_writes",
         [sys.executable, os.path.join(TOOLS_DIR,
                                       "check_atomic_writes.py")]),
        # metric-name hygiene: subsystem/name convention + every
        # literal metric documented in docs/observability.md (static
        # AST scan — cheap, always on)
        ("metric_names",
         [sys.executable, os.path.join(TOOLS_DIR,
                                       "check_metric_names.py")]),
    ]
    if not no_budget:
        gates.append(
            ("fast_tier_budget",
             [sys.executable,
              os.path.join(TOOLS_DIR, "check_fast_tier_budget.py"),
              "--log", log, "--budget", str(budget)]))
    if not no_chaos:
        # elastic chaos smoke: launcher kills a worker mid-step, the
        # relaunch resumes on a reduced mesh from a validated
        # checkpoint — the end-to-end fault-tolerance contract, run as
        # real processes on CPU (the fault-marked fast subset; the
        # 20-point randomized breadth stays in the slow tier)
        gates.append(
            ("elastic_chaos",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_elastic_chaos.py"),
              "-q", "-m", "fault and not slow",
              "-p", "no:cacheprovider"]))
        # serving chaos smoke (ISSUE 10, mirrors elastic_chaos):
        # overload + poison + wedge through the supervised engine —
        # every request completes or fails with a typed error, zero
        # leaked pages (PADDLE_TPU_SERVING_AUDIT on), no engine death.
        # The randomized sweep stays in the slow tier.
        gates.append(
            ("serving_chaos",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_serving_chaos.py"),
              "-q", "-m", "fault and not slow",
              "-p", "no:cacheprovider"]))
        # fleet chaos smoke (ISSUE 11): kill 1 of 4 replicas mid-run
        # through the ServingFleet router — zero lost or duplicated
        # completions, failover token-identity, zero leaked pages on
        # surviving replicas. The randomized kill/wedge/slow sweep
        # stays in the slow tier.
        gates.append(
            ("fleet_chaos",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_fleet_chaos.py"),
              "-q", "-m", "fault and not slow",
              "-p", "no:cacheprovider"]))
        # prefix-cache chaos smoke (ISSUE 12): a shared-prefix storm
        # with mid-run preemptions + cancellations + injected faults
        # through the supervised stack, page-accounting audit on —
        # shared pages never double-free or leak, clean streams stay
        # token-identical to the cache-off oracle. The randomized
        # sweep stays in the slow tier.
        gates.append(
            ("prefix_cache",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests",
                           "test_prefix_cache_chaos.py"),
              "-q", "-m", "fault and not slow",
              "-p", "no:cacheprovider"]))
        # process-fleet chaos (ISSUE 16): the wire fuzz + hermetic
        # ProcReplica suite, then REAL worker processes — SIGKILL 1 of
        # 4 mid-decode (breaker, zero lost/dup, token identity,
        # survivor audits over the wire) and SIGSTOP (heartbeat-timeout
        # wedge ejection + flight-recorder bundle, never the breaker).
        # The FULL proc_fleet marker, slow included: the real-process
        # tests are slow-marked for the fast-tier wall budget and this
        # gate is where they run on every pass (the observability-gate
        # pattern).
        gates.append(
            ("proc_fleet_chaos",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_wire.py"),
              os.path.join(REPO_DIR, "tests",
                           "test_proc_replica.py"),
              os.path.join(REPO_DIR, "tests",
                           "test_proc_fleet_chaos.py"),
              "-q", "-m", "proc_fleet",
              "-p", "no:cacheprovider"]))
        # disaggregated prefill/decode chaos (ISSUE 17): the fast
        # migration-primitive suite (export→import round trips, codec,
        # corrupt-block/geometry degradation, in-proc role fleet),
        # then REAL role-split workers — a prefill worker SIGKILLed
        # mid-transfer and a decode worker SIGKILLed mid-decode, both
        # with exactly-once delivery, token identity vs the colocated
        # oracle, and page audits green over the wire on every
        # survivor. The FULL disagg marker, slow included (the
        # observability-gate pattern).
        gates.append(
            ("disagg_chaos",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_disagg.py"),
              os.path.join(REPO_DIR, "tests",
                           "test_disagg_chaos.py"),
              "-q", "-m", "disagg",
              "-p", "no:cacheprovider"]))
    if not no_serving:
        # serving parity: the engine's greedy token streams must equal
        # dense model.generate's exactly — every served family with a
        # tiny preset x both pumps (run(), step()), eos stops included
        # — AND hold the 1-compiled-program budget (tiny models on CPU
        # — fast, inside the tier-1 budget tripwire)
        gates.append(
            ("serving_parity",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests",
                           "test_serving_parity.py"),
              "-q", "-m", "serving_parity",
              "-p", "no:cacheprovider"]))
        # speculative decoding (ISSUE 18): greedy spec-on streams
        # token-identical to the plain engine for BOTH draft sources
        # (n-gram prompt-lookup and self-speculative skip-layer),
        # including eos mid-chunk, forced acceptance-0/K extremes, and
        # the composition pins — spec x prefix-cache warm attach, spec
        # x priority preemption replay, spec x supervised restart —
        # plus rejection-sampler distribution exactness. The FULL
        # spec_decode marker, slow included (the observability-gate
        # pattern); rides --no-serving since it compiles the same
        # tiny-engine stack.
        gates.append(
            ("spec_decode",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests",
                           "test_spec_decode.py"),
              "-q", "-m", "spec_decode",
              "-p", "no:cacheprovider"]))
        # SLO-driven autoscaler (ISSUE 19): the control-loop unit
        # contracts (rules, hysteresis, role picks, chip cost model,
        # flapping invariant) plus the seeded production-scenario
        # suite on real tiny fleets — each scenario asserts its own
        # SLO attainment bar, the autoscaler's reaction windows, and
        # that every decision reconstructs from the /statusz log. The
        # FULL autoscale marker, slow included (the observability-gate
        # pattern); rides --no-serving with the rest of the serving
        # stack.
        gates.append(
            ("autoscale_scenarios",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_autoscaler.py"),
              os.path.join(REPO_DIR, "tests",
                           "test_autoscale_scenarios.py"),
              "-q", "-m", "autoscale",
              "-p", "no:cacheprovider"]))
        # quantized serving (ISSUE 20): the int8/fp8 KV codec bounds
        # and kernel parity, the greedy accuracy gate vs the full-
        # precision oracle on fixed-seed weights, composition with
        # everything that moves pages (prefix cache, preemption
        # replay, spec decode, disagg migration +
        # mixed-quant reject), and the weight-only int8/int4 layers.
        # The FULL quant_serving marker; rides --no-serving with the
        # rest of the serving stack.
        gates.append(
            ("quant_serving",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests",
                           "test_quant_serving.py"),
              "-q", "-m", "quant_serving",
              "-p", "no:cacheprovider"]))
    if not no_fused:
        # fused training-kernel parity: the interpret-mode kernel-vs-
        # oracle suite with every fused flag forced ON via the
        # environment (env beats any cached/tuned value by the flag-
        # precedence contract), so the gate exercises exactly the
        # configuration the compiled fit hot path runs — CPU-cheap,
        # inside the tier-1 budget tripwire
        fused_env = {"FLAGS_fused_linear_cross_entropy": "1",
                     "FLAGS_fused_rmsnorm_residual": "1",
                     "FLAGS_fused_swiglu": "1",
                     "FLAGS_fused_ce_pallas_inner": "1"}
        gates.append(
            ("fused_parity",
             ["env", *[f"{k}={v}" for k, v in fused_env.items()],
              sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests",
                           "test_fused_training_kernels.py"),
              "-q", "-m", "fused_parity",
              "-p", "no:cacheprovider"]))
    if not no_observability:
        # observability smoke (ISSUE 13): exposition endpoints stay
        # parseable + federated counters monotonic under replica
        # churn, one trace id survives preemption/failover/hedging,
        # SLO burn-rate math + alerts behave, and the bench regression
        # sentinel's --self-test passes (a marked test shells out to
        # tools/check_bench_regression.py). The FULL marker — slow
        # included: the breadth tests were moved out of tier-1 for the
        # fast-tier budget, and this gate is where they still run on
        # every gate pass
        gates.append(
            ("observability",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_exposition.py"),
              os.path.join(REPO_DIR, "tests", "test_fleet_trace.py"),
              os.path.join(REPO_DIR, "tests", "test_slo.py"),
              "-q", "-m", "observability",
              "-p", "no:cacheprovider"]))
    if not no_http:
        # HTTP front door smoke (ISSUE 15): OpenAI-compatible SSE
        # contracts (framing, option mapping, 429 Retry-After,
        # disconnect -> cancel -> page reclaim) plus the fleet-backed
        # kill-one-replica sweeps driven by the load harness — every
        # stream completes or ends typed, clean streams are
        # oracle-identical. The FULL marker, slow tests included: the
        # kill smoke and the >=64-connection full-scale sweep are
        # slow-marked for the fast-tier wall budget and this gate is
        # where they still run on every pass (the observability-gate
        # pattern).
        gates.append(
            ("http_api",
             [sys.executable, "-m", "pytest",
              os.path.join(REPO_DIR, "tests", "test_api_server.py"),
              os.path.join(REPO_DIR, "tests", "test_api_chaos.py"),
              "-q", "-m", "http_api",
              "-p", "no:cacheprovider"]))
    return gates


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="run all repo hygiene gates; fail if any fails")
    ap.add_argument("--log", default="/tmp/_t1.log",
                    help="tier-1 pytest log for the fast-tier budget "
                         "gate (default /tmp/_t1.log)")
    ap.add_argument("--budget", type=float, default=450.0,
                    help="fast-tier wall-time budget in seconds "
                         "(default 450 — calibrated to one-core box "
                         "variance, see check_fast_tier_budget.py)")
    ap.add_argument("--no-budget", action="store_true",
                    help="skip the fast-tier budget gate (no tier-1 "
                         "log in this context)")
    ap.add_argument("--no-chaos", action="store_true",
                    help="skip the chaos smokes (elastic kill-and-"
                         "resume, serving overload/poison recovery, "
                         "fleet/prefix-cache storms, process-worker "
                         "SIGKILL/SIGSTOP)")
    ap.add_argument("--no-serving", action="store_true",
                    help="skip the engine-vs-dense-generate serving "
                         "parity gate (compiles tiny engines)")
    ap.add_argument("--no-fused", action="store_true",
                    help="skip the fused training-kernel parity gate "
                         "(interpret-mode kernel suite, fused flags "
                         "forced on)")
    ap.add_argument("--no-observability", action="store_true",
                    help="skip the observability smoke gate "
                         "(exposition under churn + trace propagation "
                         "+ SLO + bench-regression self-test)")
    ap.add_argument("--no-http", action="store_true",
                    help="skip the HTTP front door smoke gate (SSE "
                         "contracts + fleet-backed kill sweep through "
                         "the API server)")
    args = ap.parse_args(argv)

    failures = 0
    for name, cmd in gate_commands(args.log, args.budget,
                                   args.no_budget, args.no_chaos,
                                   args.no_serving, args.no_fused,
                                   args.no_observability,
                                   args.no_http):
        try:
            proc = subprocess.run(cmd, capture_output=True, text=True)
            rc = proc.returncode
            tail = (proc.stdout + proc.stderr).strip().splitlines()
        except Exception as e:  # noqa: BLE001 — unrunnable == failing
            rc, tail = 1, [f"{type(e).__name__}: {e}"]
        status = "PASS" if rc == 0 else f"FAIL (rc={rc})"
        print(f"[gate] {name}: {status}")
        if rc != 0:
            failures += 1
            for line in tail[-20:]:
                print(f"    {line}")
    if failures:
        print(f"[gate] {failures} gate(s) failed")
        return 1
    print("[gate] all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
