#!/usr/bin/env python
"""Bench regression sentinel (ISSUE 13): compare a fresh bench record
against the BENCH_r*.json trajectory and fail on a perf drop.

The decode number sat flat at ~2,254 tok/s for several rounds and only
a human reading JSON noticed — exactly the job of a machine gate. This
tool:

1. loads the repo's bench trajectory (``BENCH_r*.json``, driver
   wrappers ``{cmd, parsed, rc, tail}`` and raw record lines both
   accepted; rounds whose ``parsed`` is null — outage rounds — are
   skipped);
2. takes the FRESH record (``--fresh FILE``; default: the newest
   trajectory round with a parsed record, compared against the rounds
   before it);
3. for every key in the PER-KEY TOLERANCE TABLE present in the fresh
   record, finds the most recent COMPARABLE baseline round carrying
   that key and fails (exit 1) when
   ``fresh < baseline * (1 - tolerance)``.

Provenance-aware: records stamped with ``provenance.backend`` (PR 9)
are only compared against records on the SAME backend — a CPU-smoke
record can never "regress" against a TPU round. Records predating the
provenance stamp (r01–r03) have an unknown backend, which is treated
as compatible: the historical trajectory was captured by one driver
environment, and skipping unknowns would make the whole gate vacuous.
Improvements are reported informationally; only drops past tolerance
fail.

``--self-test`` runs the built-in synthetic scenarios (a 20% decode
drop must flag; an in-tolerance wobble must pass; a cross-backend drop
must be skipped) — wired into the ``observability`` CI gate
(tools/run_gates.py) so the sentinel itself cannot rot.

Usage::

    python tools/check_bench_regression.py                 # trajectory
    python tools/check_bench_regression.py --fresh new.json
    python tools/check_bench_regression.py --self-test

Exit codes: 0 = no regression, 1 = regression (or broken self-test),
2 = usage/IO error.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: higher-is-better keys -> max tolerated fractional DROP vs the most
#: recent comparable baseline. Train/decode are tight (stable
#: single-program measurements); serving-stack numbers carry more
#: scheduling noise; ratio keys (vs_*) are diagnostics, not gated.
TOLERANCES = {
    "value": 0.10,                  # train tokens/s/chip (headline)
    "decode_value": 0.10,           # the flat-at-2254 number
    "cb_value": 0.20,               # continuous batching tok/s
    "cb_unified_tok_s": 0.20,
    "moe_value": 0.15,
    "moe_decode_value": 0.20,
    "train_e2e_tokens_per_sec": 0.15,
    "cb_overload_tok_s": 0.25,
    "cb_fleet_tok_s": 0.25,
    "cb_prefix_warm_tok_s": 0.25,
    "obs_slo_attainment": 0.10,     # SLO attainment is a perf claim too
    # HTTP front door (ISSUE 15): client-observed delivery through the
    # API server. Tok/s gets the serving-section tolerance (single-core
    # boxes drift); goodput is a correctness-adjacent claim and gets a
    # tight one. cb_http_vs_engine is a vs_* ratio — never gated.
    "cb_http_tok_s": 0.25,
    "cb_http_goodput_frac": 0.10,
    # process-backed fleet (ISSUE 16): real worker processes + a
    # mid-run SIGKILL — the noisiest serving section (spawn, wire,
    # respawn, failover all inside the timed region) gets the loosest
    # serving tolerance; goodput through the front-door smoke stays a
    # correctness-adjacent claim. cb_procfleet_vs_inproc is a vs_*
    # ratio — never gated.
    "cb_procfleet_tok_s": 0.30,
    "cb_procfleet_http_goodput_frac": 0.10,
    # disaggregated prefill/decode (ISSUE 17): process workers + KV
    # migration inside the timed region — procfleet-class noise. The
    # latency keys (p99_ttft, migration_ms) are lower-is-better and
    # out of this table's frame; cb_disagg_vs_colocated is a vs_*
    # ratio — never gated.
    "cb_disagg_tok_s": 0.30,
    # speculative decoding (ISSUE 18): spec-vs-plain A/B at decode
    # batch 1. Tok/s gets the serving-section tolerance; HTTP goodput
    # stays a correctness-adjacent claim. cb_spec_vs_plain and
    # cb_spec_http_vs_plain are vs_* ratios — never gated — and
    # cb_spec_accept_rate / cb_spec_itl_ms_p99 are workload-dependent
    # diagnostics (ITL is lower-is-better, out of this table's frame).
    "cb_spec_tok_s": 0.25,
    "cb_spec_http_goodput_frac": 0.10,
    # SLO-driven autoscaler (ISSUE 19): scenario A/B vs a max-size
    # fixed fleet. Goodput and SLO attainment are correctness-adjacent
    # claims; autoscale_chip_seconds is lower-is-better (out of this
    # table's frame), autoscale_decisions is a count diagnostic and
    # autoscale_vs_fixed_chips is a vs_* ratio — never gated.
    "autoscale_goodput_frac": 0.10,
    "autoscale_slo_attainment": 0.10,
    # quantized serving (ISSUE 20): the int8-KV leg's tok/s gets the
    # serving-section tolerance; the greedy top-1 agreement keys are
    # the accuracy gate's bench-side echo — correctness-adjacent,
    # tight. cb_quant_capacity_ratio and the other *_ratio keys move
    # with the host's pool dtype (f32 pools on the CPU smoke, bf16 on
    # TPU) and are never gated; cb_quant_ppl_delta is a signed
    # diagnostic outside this table's higher-is-better frame.
    "cb_quant_tok_s": 0.25,
    "cb_quant_top1_agreement": 0.02,
    "cb_quant_weight_top1_agreement": 0.02,
}


def load_record(path):
    """One bench artifact -> (record dict | None, label). Driver
    wrappers are unwrapped; a null ``parsed`` (outage round) is None.

    PARTIAL records are first-class (ISSUE 18): bench.py re-prints the
    running record after every section and flushes it atomically, so a
    timed-out round's artifact may be a multi-line capture whose final
    line was cut mid-write — the LAST complete JSON object line wins
    (it carries every section measured before the cut). check() then
    compares whatever keys it has; absent keys simply aren't gated."""
    with open(path, encoding="utf-8") as f:
        text = f.read()
    label = os.path.basename(path)
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue        # section telemetry / stderr bleed
            try:
                cand = json.loads(line)
            except ValueError:
                continue        # the truncated tail of a killed round
            if isinstance(cand, dict):
                doc = cand
        if doc is None:
            raise
    if isinstance(doc, dict) and "parsed" in doc and "rc" in doc:
        return doc["parsed"], label
    return doc if isinstance(doc, dict) else None, label


def backend_of(record):
    """The record's provenance backend, or None for pre-PR-9 records
    (unknown; treated as comparable — see module docstring)."""
    prov = record.get("provenance")
    if isinstance(prov, dict):
        return prov.get("backend")
    return None


def comparable(fresh_backend, base_backend):
    """Skip ONLY when both backends are known and differ."""
    if fresh_backend is None or base_backend is None:
        return True
    return fresh_backend == base_backend


def check(fresh, baselines, tolerances=None, out=sys.stdout):
    """Compare one fresh record against a list of (record, label)
    baselines, oldest first. Returns the list of regression strings
    (empty = pass); prints one line per checked key."""
    tolerances = TOLERANCES if tolerances is None else tolerances
    fb = backend_of(fresh)
    regressions = []
    checked = 0
    for key, tol in sorted(tolerances.items()):
        v = fresh.get(key)
        if not isinstance(v, (int, float)):
            continue
        base = None
        for rec, label in reversed(baselines):
            bv = rec.get(key)
            if not isinstance(bv, (int, float)) or bv <= 0:
                continue
            if not comparable(fb, backend_of(rec)):
                print(f"[bench-regr] {key}: skipped {label} "
                      f"(backend {backend_of(rec)!r} != {fb!r})",
                      file=out)
                continue
            base = (bv, label)
            break
        if base is None:
            continue
        bv, label = base
        checked += 1
        floor = bv * (1.0 - tol)
        delta = (v - bv) / bv
        status = "OK"
        if v < floor:
            status = "REGRESSION"
            regressions.append(
                f"{key}: {v} vs {bv} ({label}) — "
                f"{delta:+.1%} exceeds -{tol:.0%} tolerance")
        print(f"[bench-regr] {key}: {v} vs {bv} ({label}) "
              f"{delta:+.1%} [{status}]", file=out)
    if checked == 0:
        print("[bench-regr] no comparable keys found — nothing gated",
              file=out)
    return regressions


def load_trajectory(pattern):
    paths = sorted(glob.glob(pattern))
    out = []
    for p in paths:
        try:
            rec, label = load_record(p)
        except (OSError, ValueError) as e:
            print(f"[bench-regr] {p}: unreadable ({e}) — skipped",
                  file=sys.stderr)
            continue
        if rec is None:
            print(f"[bench-regr] {os.path.basename(p)}: no parsed "
                  "record (outage round) — skipped", file=sys.stderr)
            continue
        out.append((rec, label))
    return out


def self_test() -> int:
    """The sentinel's own gate: synthetic trajectories with known
    answers. Exit 0 iff every scenario behaves."""
    import io
    base = [({"decode_value": 2254.0, "value": 8184.0,
              "provenance": {"backend": "tpu"}}, "BENCH_sym1.json")]
    ok = True

    def expect(name, fresh, want_regr):
        nonlocal ok
        regs = check(fresh, base, out=io.StringIO())
        got = bool(regs)
        verdict = "ok" if got == want_regr else "FAILED"
        if got != want_regr:
            ok = False
        print(f"[self-test] {name}: expected "
              f"{'regression' if want_regr else 'pass'}, got "
              f"{'regression' if got else 'pass'} [{verdict}]")

    # the acceptance scenario: a 20% decode tok/s drop must flag
    expect("20% decode drop",
           {"decode_value": 2254.0 * 0.80,
            "provenance": {"backend": "tpu"}}, True)
    expect("in-tolerance wobble (-5%)",
           {"decode_value": 2254.0 * 0.95,
            "provenance": {"backend": "tpu"}}, False)
    expect("cross-backend drop skipped",
           {"decode_value": 30.0,
            "provenance": {"backend": "cpu"}}, False)
    expect("unknown-provenance fresh compares",
           {"decode_value": 2254.0 * 0.5}, True)
    expect("improvement passes",
           {"decode_value": 2254.0 * 1.3,
            "provenance": {"backend": "tpu"}}, False)
    # ratio keys and unknown keys are never gated
    expect("untracked keys ignored",
           {"cb_unified_vs_legacy": 0.01,
            "provenance": {"backend": "tpu"}}, False)
    # partial records (ISSUE 18): a round cut after the train section
    # gates ONLY the keys it carries — missing decode/cb keys are not
    # failures — and a real drop in a carried key still flags
    expect("partial record, carried key ok",
           {"value": 8184.0,
            "provenance": {"backend": "tpu"}}, False)
    expect("partial record, carried key drops",
           {"value": 8184.0 * 0.7,
            "provenance": {"backend": "tpu"}}, True)
    # a timed-out round's artifact: incremental record lines with a
    # truncated tail must parse to the last COMPLETE line
    import tempfile
    good = {"decode_value": 2254.0 * 0.99,
            "provenance": {"backend": "tpu"}}
    capture = (json.dumps({"value": 8184.0}) + "\n"
               + json.dumps(good) + "\n"
               + json.dumps({"decode_value": 1.0})[:12] + "\n")
    with tempfile.NamedTemporaryFile(
            "w", suffix=".json", delete=False) as tf:
        tf.write(capture)
        trunc_path = tf.name
    try:
        rec, _ = load_record(trunc_path)
        got = rec == good
        print(f"[self-test] truncated multi-line capture: expected "
              f"last complete line, got "
              f"{'it' if got else rec!r} [{'ok' if got else 'FAILED'}]")
        if not got:
            ok = False
        regs = check(rec, base, out=__import__('io').StringIO())
        if regs:
            ok = False
            print("[self-test] truncated capture wrongly flagged "
                  "[FAILED]")
    finally:
        os.unlink(trunc_path)
    print(f"[self-test] {'all scenarios behave' if ok else 'BROKEN'}")
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fail when a fresh bench record regresses vs the "
                    "BENCH_r0*.json trajectory")
    ap.add_argument("--fresh", default=None,
                    help="path to the fresh record (driver wrapper or "
                         "raw record JSON); default: the newest "
                         "trajectory round, checked against the "
                         "rounds before it")
    ap.add_argument("--glob", default=os.path.join(REPO,
                                                   "BENCH_r*.json"),
                    help="trajectory glob (default ./BENCH_r*.json — "
                         "NOT 'r0*', which would silently stop "
                         "matching at round 10)")
    ap.add_argument("--self-test", action="store_true",
                    help="run the built-in synthetic scenarios")
    args = ap.parse_args(argv)

    if args.self_test:
        return self_test()

    trajectory = load_trajectory(args.glob)
    if not trajectory:
        print(f"[bench-regr] no parsed trajectory record matches "
              f"{args.glob} — nothing to compare", file=sys.stderr)
        return 0
    if args.fresh is not None:
        try:
            fresh, flabel = load_record(args.fresh)
        except (OSError, ValueError) as e:
            print(f"[bench-regr] --fresh {args.fresh}: {e}",
                  file=sys.stderr)
            return 2
        if fresh is None:
            print(f"[bench-regr] --fresh {args.fresh}: no parsed "
                  "record", file=sys.stderr)
            return 2
        # a fresh record already committed into the trajectory must
        # not be compared against ITSELF (delta +0.0% would mask the
        # exact regression the sentinel exists to catch)
        fresh_real = os.path.realpath(args.fresh)
        baselines = [(rec, label) for rec, label in trajectory
                     if os.path.realpath(
                         os.path.join(os.path.dirname(args.glob) or
                                      ".", label)) != fresh_real
                     and label != flabel]
    else:
        if len(trajectory) < 2:
            print("[bench-regr] fewer than 2 parsed trajectory "
                  "records — nothing to compare", file=sys.stderr)
            return 0
        (fresh, flabel) = trajectory[-1]
        baselines = trajectory[:-1]

    print(f"[bench-regr] fresh={flabel} vs {len(baselines)} "
          f"baseline round(s)")
    regressions = check(fresh, baselines)
    if regressions:
        for r in regressions:
            print(f"[bench-regr] REGRESSION: {r}", file=sys.stderr)
        return 1
    print("[bench-regr] no regression")
    return 0


if __name__ == "__main__":
    sys.exit(main())
