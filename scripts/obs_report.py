#!/usr/bin/env python
"""obs_report — render an observability snapshot into a human
training-health report, and diff two snapshots for regression checks.

Input formats (auto-detected):

* a registry JSONL written by ``MetricsRegistry.write_jsonl`` (one
  ``{"wall_time", "metrics"}`` line per scrape — the LAST line is
  reported);
* ``bench_metrics.json`` (``{workload: {..., "metrics": snapshot}}`` —
  pick one with ``--workload``, default: every workload in the file);
* a bare registry snapshot dict (``/metrics.json`` saved to a file).

Optionally pair it with a Chrome trace (``--trace trace.json``, from
``Tracer.export_chrome_trace`` or the ``/trace`` endpoint) for a
span-aggregation table.

Diff mode: ``obs_report.py CURRENT --diff BASELINE`` compares the two
snapshots and exits 1 when a higher-is-better metric (throughput, MFU)
dropped, or a latency p50 rose, by more than ``--threshold`` (default
10%) — the offline half of ``bench.py --compare``.

Loadtest mode (auto-detected): a ``zoo-loadtest`` report JSON
(``scripts/zoo-loadtest ... --out report.json``) renders its SLO
verdict and the capacity-planning table (replicas needed per req/s at
the target p99), then falls through to the standard report over the
run's embedded registry snapshot (loadgen latency histograms etc.).

Multi-host mode: ``obs_report.py --merge-hosts <run_dir>`` federates a
launcher run directory (one ``host-<k>/`` slot per worker, written by
``zoo-launch --run-dir``): per-host step-time skew table, named
straggler, pipeline bubble fraction, collective byte/time accounting,
cluster-summed counters, and ONE merged Chrome trace aligned on the
launcher's clock anchor (``<run_dir>/merged_trace.json``).

Incident mode: ``obs_report.py --incident <run_dir>`` renders the
zoo-doctor forensics view — the causally-ordered incident timeline
joined from flight-recorder journals, heartbeats, blackboxes, the
degraded record and tsdb SLO state, plus the ranked root-cause
hypothesis list with evidence citations (reuses ``incident.json``
when a prior ``zoo-doctor`` run left one in the run dir).

Examples::

    python scripts/obs_report.py metrics.jsonl --trace trace.json
    python scripts/obs_report.py bench_metrics.json --workload ncf
    python scripts/obs_report.py run2.jsonl --diff run1.jsonl
    python scripts/obs_report.py --merge-hosts /runs/exp7
    python scripts/obs_report.py --incident /runs/exp7

Pure stdlib + file IO; never imports jax (usable on a laptop against
artifacts scp'd from the pod).  The merge logic lives in
``analytics_zoo_tpu/observability/aggregator.py`` — itself stdlib-only
— which this script loads DIRECTLY BY FILE PATH so the package (and
its jax import) never loads.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple


# --------------------------------------------------------------- loading
def _is_snapshot(d) -> bool:
    return isinstance(d, dict) and (
        "counters" in d or "gauges" in d or "histograms" in d)


def load_snapshots(path: str, workload: Optional[str] = None
                   ) -> List[Tuple[str, Dict]]:
    """Return ``[(label, snapshot), ...]`` from any supported file."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if doc is not None:
        if _is_snapshot(doc):
            return [(path, doc)]
        if isinstance(doc, dict) and "metrics" in doc \
                and _is_snapshot(doc["metrics"]):
            return [(path, doc["metrics"])]
        if isinstance(doc, dict):   # bench_metrics.json shape
            out = []
            for name, entry in sorted(doc.items()):
                snap = entry.get("metrics") \
                    if isinstance(entry, dict) else None
                if _is_snapshot(snap) and (workload is None
                                           or name == workload):
                    out.append((name, snap))
            if out:
                return out
        raise SystemExit(f"{path}: unrecognized snapshot format")
    # JSONL: report the last parseable line
    last = None
    for line in text.splitlines():
        line = line.strip()
        if not line:
            continue
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if _is_snapshot(rec):
            last = rec
        elif isinstance(rec, dict) and _is_snapshot(rec.get("metrics")):
            last = rec["metrics"]
    if last is None:
        raise SystemExit(f"{path}: no registry snapshot found")
    return [(path, last)]


# ------------------------------------------------------------- selectors
def _labeled(series: Dict, prefix: str) -> List[Tuple[str, object]]:
    """Entries of a snapshot section whose key is ``prefix`` or
    ``prefix{label=...}``; returns (label-or-'', value)."""
    out = []
    for key, val in sorted(series.items()):
        if key == prefix:
            out.append(("", val))
        elif key.startswith(prefix + "{"):
            out.append((key[len(prefix) + 1:-1], val))
    return out


def _fmt_seconds(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v >= 1.0:
        return f"{v:.2f}s"
    if v >= 1e-3:
        return f"{v * 1e3:.1f}ms"
    return f"{v * 1e6:.0f}us"


def _table(rows: List[List[str]], header: List[str]) -> str:
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    def fmt(r):
        return "  ".join(str(c).ljust(w) for c, w in zip(r, widths))
    sep = "  ".join("-" * w for w in widths)
    return "\n".join([fmt(header), sep] + [fmt(r) for r in rows])


def _label_value(lab: str) -> str:
    return lab.split("=", 1)[-1].strip('"') if lab else "?"


def _startup_timeline(counters: Dict, gauges: Dict) -> List[str]:
    """The job's two edges (docs/observability.md, "The start-up
    timeline"): the start-up spans' seconds, the time to the first
    finished step, and the exact compile stages by function."""
    own = {_label_value(lab): v for lab, v in
           _labeled(counters, "span_self_seconds_total")}
    total = {_label_value(lab): v for lab, v in
             _labeled(counters, "span_seconds_total")}
    edge = sorted((n for n in total if n.startswith("startup_")
                   or n in ("train_startup", "aot_warm_start",
                            "train_return")),
                  key=lambda n: -own.get(n, 0.0))
    lines: List[str] = []
    if edge:
        lines += ["", "start-up timeline (self = less the spans nested "
                  "in it; train_startup's self is what no phase names):",
                  _table([[n, f"{total[n]:.2f}s",
                           f"{own.get(n, 0.0):.2f}s"] for n in edge],
                         ["span", "seconds", "self"])]
    first = gauges.get("train_time_to_first_step_seconds")
    if first:
        lines.append(f"time to first step: {first:.2f}s (newest train())")
    stages: Dict[str, List[float]] = {}
    for i, family in enumerate(("jax_traces_total",
                                "jax_trace_seconds_total",
                                "jax_lower_seconds_total")):
        for lab, v in _labeled(counters, family):
            stages.setdefault(_label_value(lab), [0.0, 0.0, 0.0])[i] = v
    if stages:
        top = sorted(stages.items(), key=lambda kv: -(kv[1][1] + kv[1][2]))
        lines += ["", "compile stages (exact, jax.monitoring; each less "
                  "the stages nested in it; first-call wall above is an "
                  f"upper bound): {len(top)} functions, "
                  f"{sum(v[1] for v in stages.values()):.2f}s tracing, "
                  f"{sum(v[2] for v in stages.values()):.2f}s lowering",
                  _table([[fn, int(v[0]), f"{v[1]:.2f}s", f"{v[2]:.2f}s"]
                          for fn, v in top[:8]],
                         ["function", "traces", "trace", "lower"])]
    load = counters.get("compile_cache_load_seconds_total")
    if load:
        lines.append(f"executable cache reads: {load:.2f}s of the "
                     "backend compile seconds")
    for lab, n in _labeled(counters, "train_program_traces_total"):
        lines.append(f"train program [{_label_value(lab)}]: traced "
                     f"{int(n)} time(s)")
    return lines


# --------------------------------------------------------------- report
def render_report(label: str, snap: Dict,
                  trace_events: Optional[List[Dict]] = None) -> str:
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})
    lines: List[str] = [f"== training-health report: {label} =="]

    # ---- step-time attribution ------------------------------------
    attr = _labeled(hists, "train_step_time_seconds")
    if attr:
        total_time = sum(h["sum"] for _, h in attr) or 1e-12
        rows = []
        for lab, h in attr:
            comp = _label_value(lab)
            rows.append([
                comp, h["count"], _fmt_seconds(h["p50"]),
                _fmt_seconds(h["p95"]), f"{h['sum']:.2f}s",
                f"{100 * h['sum'] / total_time:.0f}%"])
        lines += ["", "step-time attribution "
                  "(device is sampled — compare p50s, not sums):",
                  _table(rows, ["component", "count", "p50", "p95",
                                "total", "share"])]
    step_lat = _labeled(hists, "train_step_latency_seconds")
    for lab, h in step_lat:
        lines.append(
            f"step latency [{lab or 'all'}]: p50 "
            f"{_fmt_seconds(h['p50'])}  p95 {_fmt_seconds(h['p95'])}  "
            f"({h['count']} steps)")

    # ---- throughput / MFU -----------------------------------------
    tput = gauges.get("train_throughput_samples_per_sec")
    if tput:
        lines.append(f"throughput: {tput:.1f} samples/s "
                     f"(last epoch)")
    mfu = gauges.get("train_mfu")
    dev_step = gauges.get("train_device_step_seconds")
    flops = _labeled(gauges, "train_step_flops")
    if mfu:
        lines.append(
            f"MFU: {100 * mfu:.1f}% of chip peak "
            f"(sampled device step {_fmt_seconds(dev_step)})")
    elif flops:
        lines.append(
            "MFU: not computed (unknown chip peak — set "
            "observability.peak_flops); cost-analysis FLOPs known: "
            + ", ".join(f"{lab}={v:.3g}" for lab, v in flops))

    # ---- compilation ----------------------------------------------
    comp_rows = []
    for lab, n in _labeled(counters, "jax_compiles_total"):
        fn = _label_value(lab)
        secs = dict(_labeled(counters, "jax_compile_seconds_total")
                    ).get(lab, 0.0)
        rec = dict(_labeled(counters, "jax_recompiles_total")
                   ).get(lab, 0)
        comp_rows.append([fn, int(n), f"{secs:.2f}s", int(rec)])
    if comp_rows:
        lines += ["", "compilation (recompiles>0 after warmup = churn "
                  "— a shape/dtype drifts between steps):",
                  _table(comp_rows, ["function", "compiles",
                                     "first-call wall", "recompiles"])]
    backend_s = counters.get("jax_backend_compile_seconds_total")
    if backend_s:
        lines.append(
            f"backend compile: "
            f"{int(counters.get('jax_backend_compiles_total', 0))} "
            f"XLA compilations, {backend_s:.2f}s total")
    # ---- JAX's persistent compilation cache (docs/aot-compile.md) --
    hits = counters.get("compile_cache_hits_total", 0)
    misses = counters.get("compile_cache_misses_total", 0)
    if hits or misses:
        rate = 100.0 * hits / (hits + misses)
        lines.append(
            f"executable cache: {int(hits)} hit(s) / {int(misses)} "
            f"miss(es) ({rate:.0f}% hit rate)")

    lines += _startup_timeline(counters, gauges)

    # ---- fused kernel suite / roofline (docs/perf-tuning.md) -------
    builds = _labeled(counters, "fused_kernel_builds_total")
    if builds:
        saved = {}
        for lab, v in _labeled(gauges, "kernel_bytes_saved_per_step"):
            saved[lab.split("=", 1)[-1].strip('"')] = v
        roof = {}
        for lab, v in _labeled(gauges, "kernel_roofline_attainment"):
            roof[lab.split("=", 1)[-1].strip('"')] = v
        per_kernel: Dict[str, Dict[str, int]] = {}
        for lab, n in builds:
            parts = dict(p.split("=", 1) for p in lab.split(","))
            k = parts.get("kernel", "?").strip('"')
            path = parts.get("path", "?").strip('"')
            per_kernel.setdefault(k, {})[path] = int(n)
        rows = []
        for k in sorted(set(per_kernel) | set(saved) | set(roof)):
            paths = per_kernel.get(k, {})
            path = "+".join(sorted(paths)) or "-"
            sv = saved.get(k)
            rf = roof.get(k)
            rows.append([
                k, path, sum(paths.values()),
                _fmt_bytes(sv) + "/step" if sv else "-",
                f"{rf:.2f}x" if rf is not None else "-"])
        lines += ["", "fused kernel suite (path=lax: XLA fuses the same "
                  "math — the optimizer update's only form, and an "
                  "epilogue's where the Pallas probe declined; roofline "
                  "1.0 = HBM-bandwidth-bound floor reached):",
                  _table(rows, ["kernel", "path", "builds",
                                "bytes saved", "roofline"])]

    # ---- health ----------------------------------------------------
    nonfinite = _labeled(counters, "train_nonfinite_total")
    events = _labeled(counters, "watchdog_events_total")
    status = gauges.get("train_health_status", 0)
    verdict = {0: "healthy", 1: "warned", 2: "HALTED"}.get(
        int(status), "?")
    lines += ["", f"health: {verdict}"]
    for lab, n in nonfinite:
        lines.append(f"  non-finite steps [{lab}]: {int(n)}")
    for lab, n in events:
        lines.append(f"  watchdog events [{lab}]: {int(n)}")
    retries = counters.get("train_retry_total")
    if retries:
        lines.append(f"  retry-loop restores: {int(retries)}")

    # ---- input pipeline -------------------------------------------
    waits = _labeled(hists, "data_batch_wait_seconds")
    for lab, h in waits:
        lines.append(
            f"data wait [{lab or 'pipeline'}]: p50 "
            f"{_fmt_seconds(h['p50'])}  p95 {_fmt_seconds(h['p95'])} "
            f"({h['count']} batches)")

    # ---- device ----------------------------------------------------
    in_use = _labeled(gauges, "device_bytes_in_use")
    limit = dict(_labeled(gauges, "device_bytes_limit"))
    for lab, v in in_use:
        cap = limit.get(lab)
        pct = f" ({100 * v / cap:.0f}% of limit)" if cap else ""
        lines.append(f"HBM in use [{lab}]: {v / (1 << 30):.2f} GiB{pct}")
    stale = [lab for lab, v in
             _labeled(gauges, "device_telemetry_stale") if v]
    if stale:
        lines.append(f"  STALE telemetry on device(s): {stale}")

    # ---- trace aggregation ----------------------------------------
    if trace_events:
        agg: Dict[str, List[float]] = {}
        for e in trace_events:
            if e.get("ph") == "X":
                agg.setdefault(e["name"], []).append(
                    e.get("dur", 0.0) / 1e6)
        rows = [[name, len(durs), _fmt_seconds(sum(durs) / len(durs)),
                 f"{sum(durs):.2f}s"]
                for name, durs in sorted(
                    agg.items(), key=lambda kv: -sum(kv[1]))[:12]]
        if rows:
            lines += ["", "trace spans (top by total time):",
                      _table(rows, ["span", "count", "mean", "total"])]
    return "\n".join(lines)


# ------------------------------------------------------------- loadtest
def _peek_loadtest(path: Optional[str]) -> Optional[Dict]:
    """The loadtest-report document, when ``path`` is one (the
    ``kind`` tag, or a capacity_planning section); None otherwise."""
    if not path:
        return None
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, json.JSONDecodeError):
        return None
    if isinstance(doc, dict) and (
            doc.get("kind") == "zoo_loadtest_report"
            or "capacity_planning" in doc):
        return doc
    return None


def render_loadtest_report(label: str, doc: Dict) -> str:
    """Render a ``zoo-loadtest`` report document: the SLO verdict
    check-by-check, then the capacity-planning table fitted from the
    run's ramp."""
    lines = [f"== loadtest report: {label} "
             f"(scenario {doc.get('scenario', '?')}) =="]
    verdict = doc.get("verdict") or {}
    lines.append(f"verdict: "
                 f"{'PASS' if verdict.get('passed') else 'FAIL'}")
    for c in verdict.get("checks", []):
        mark = ("SKIP" if c.get("skipped")
                else "ok  " if c.get("passed") else "FAIL")
        lines.append(f"  [{mark}] {c.get('name')}: {c.get('detail')}")
    lat = verdict.get("latency") or {}
    if lat:
        lines.append(
            "latency (from SCHEDULED is the coordinated-omission-"
            "safe basis the verdict gates on): "
            + "  ".join(f"{k}={v:.1f}ms"
                        for k, v in sorted(lat.items())))
    cap = doc.get("capacity_planning") or {}
    rows = [[w["window_s"][0], w["offered_rps"], w["replicas"],
             w["rps_per_replica"], w["p99_from_scheduled_ms"],
             "yes" if w["met_slo"] else "NO"]
            for w in cap.get("windows", [])]
    if rows:
        lines += ["", f"capacity fit (target p99 <= "
                  f"{cap.get('target_p99_ms', 0):.0f}ms):",
                  _table(rows, ["t0", "offered rps", "replicas",
                                "rps/replica", "p99 ms", "met SLO"])]
    per = cap.get("rps_per_replica_at_slo")
    if per:
        needed = cap.get("replicas_for", {})
        lines.append(
            f"plan: {per:.1f} req/s per replica at the target — "
            + "  ".join(f"{k}rps needs {v}"
                        for k, v in needed.items()))
    else:
        lines.append("plan: NO window met the target SLO — the fit "
                     "has no feasible point (add capacity or relax "
                     "the target)")
    return "\n".join(lines)


# ---------------------------------------------------------- req forensics
def _segments(stations: List[Dict]) -> List[Tuple[str, float, float, Dict]]:
    """(station, offset_s, segment_s, attrs) per mark, time-ordered.
    A segment is the time since the previous station — the wait the
    request spent to REACH this station — so the segments sum to the
    timeline's measured latency by construction."""
    marks = sorted(stations, key=lambda s: float(s.get("t", 0.0)))
    out = []
    prev = marks[0].get("t", 0.0) if marks else 0.0
    for m in marks:
        t = float(m.get("t", 0.0))
        attrs = {k: v for k, v in m.items()
                 if k not in ("station", "t")}
        out.append((m.get("station", "?"), t, max(t - prev, 0.0),
                    attrs))
        prev = t
    return out


def render_requests_report(label: str, doc: Dict,
                           top: int = 10) -> str:
    """The slowest-request waterfall: per-station breakdown of where
    each tail request's time went, plus the aggregate station profile
    of the tail.  ``doc`` is a merged ``requests.json`` document
    (``aggregator.merge_requests``)."""
    tls = doc.get("timelines") or []
    lines = [f"== request forensics: {label} =="]
    hosts = doc.get("hosts_merged")
    lines.append(
        f"{len(tls)} timeline(s) kept"
        + (f" across {hosts} host(s)" if hosts else "")
        + f"; sampler kept {doc.get('kept', len(tls))} / dropped "
          f"{doc.get('dropped', 0)} (tail-based: errors/sheds/"
          f"quarantines + slowest-K always survive)")
    by_outcome: Dict[str, int] = {}
    for tl in tls:
        oc = tl.get("outcome", "?")
        by_outcome[oc] = by_outcome.get(oc, 0) + 1
    if by_outcome:
        lines.append("outcomes: " + "  ".join(
            f"{k}={v}" for k, v in sorted(by_outcome.items())))
    if not tls:
        lines.append("no timelines — was the run traced? "
                     "(observability.reqtrace on, requests.json "
                     "flushed/exported)")
        return "\n".join(lines)

    ranked = sorted(tls, key=lambda t: -float(t.get("latency_s", 0.0)))
    shown = ranked[:top]
    # aggregate tail profile: which station dominates the slow set
    agg: Dict[str, float] = {}
    for tl in shown:
        for st, _off, seg, _a in _segments(tl.get("stations") or []):
            agg[st] = agg.get(st, 0.0) + seg
    lines.append("")
    lines.append(f"slowest {len(shown)} request(s) — station "
                 f"waterfall (segment = time to REACH the station; "
                 f"segments sum to the measured latency):")
    for i, tl in enumerate(shown, 1):
        segs = _segments(tl.get("stations") or [])
        lat = float(tl.get("latency_s", 0.0))
        dominant = max(segs, key=lambda s: s[2])[0] if segs else "-"
        lines.append(
            f"\n#{i}  trace {tl.get('trace_id', '?')}  "
            f"[{tl.get('outcome', '?')}]  "
            f"{tl.get('transport') or '?'}:"
            f"{tl.get('endpoint') or 'default'}  "
            f"latency {_fmt_seconds(lat)}  dominant={dominant}")
        rows = []
        for st, off, seg, attrs in segs:
            extra = "  ".join(f"{k}={v}" for k, v
                              in sorted(attrs.items()))
            bar = "#" * min(int(round(40 * seg / lat))
                            if lat > 0 else 0, 40)
            rows.append([st, f"+{_fmt_seconds(off)}",
                         _fmt_seconds(seg), bar, extra])
        lines.append(_table(rows, ["station", "offset", "segment",
                                   "", "attrs"]))
        ssum = sum(s[2] for s in segs)
        lines.append(f"    segments sum {_fmt_seconds(ssum)} vs "
                     f"measured {_fmt_seconds(lat)}")
    total = sum(agg.values()) or 1e-12
    rows = [[st, _fmt_seconds(v), f"{100 * v / total:.0f}%"]
            for st, v in sorted(agg.items(), key=lambda kv: -kv[1])]
    lines += ["", "tail profile (summed over the slowest set — the "
              "station to fix first):",
              _table(rows, ["station", "total", "share"])]
    return "\n".join(lines)


# ----------------------------------------------------------------- SLO
def _load_obs_module(name: str):
    """Load ``observability/<name>.py`` by FILE PATH (tsdb/slo/drift
    are stdlib-only by contract) — the same jax-free trick as the
    aggregator loader."""
    import importlib.util
    modname = f"_zoo_{name}"
    if modname in sys.modules:
        return sys.modules[modname]
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "analytics_zoo_tpu", "observability", f"{name}.py")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _spark(values: List[float], width: int = 40) -> str:
    """A one-line ASCII timeline: 8-level bars, newest right."""
    if not values:
        return ""
    if len(values) > width:
        step = len(values) / width
        values = [values[int(i * step)] for i in range(width)]
    lo, hi = min(values), max(values)
    span = (hi - lo) or 1.0
    bars = " .:-=+*#@"
    return "".join(
        bars[int((v - lo) / span * (len(bars) - 1))] for v in values)


def _find_slo_spec(target: str, explicit: Optional[str]) -> Optional[str]:
    """--slo-spec wins; else slo.yaml beside the run dir, else the
    repo's checked-in slo.yaml."""
    if explicit:
        return explicit
    candidates = [os.path.join(target, "slo.yaml")] \
        if os.path.isdir(target) else []
    candidates.append(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "slo.yaml"))
    for c in candidates:
        if os.path.isfile(c):
            return c
    return None


def render_incident_report(target: str) -> str:
    """The ``--incident`` section: zoo-doctor's causally-ordered
    timeline + ranked root-cause hypotheses for a finished run dir.
    Renders an existing ``incident.json`` (a file, or one inside the
    run dir) without re-diagnosing; otherwise runs the diagnoser
    in-process.  Entirely jax-free: incident loads by file path."""
    inc = _load_obs_module("incident")
    if os.path.isfile(target):
        with open(target) as f:
            doc = json.load(f)
    else:
        existing = os.path.join(target, "incident.json")
        if os.path.isfile(existing):
            with open(existing) as f:
                doc = json.load(f)
        else:
            doc = inc.diagnose(target)
    return inc.render_incident(doc)


def render_slo_report(target: str,
                      spec_path: Optional[str] = None) -> str:
    """The ``--slo`` section: error-budget timelines, burn-rate
    tables, alert transitions, and drift callouts — from a run dir's
    tsdb segments (``host-<k>/tsdb/``) or a ``slo_report.json``
    written by ``zoo-loadtest --slo-out``.  Entirely jax-free: tsdb/
    slo/drift load by file path."""
    # a slo_report.json document renders directly
    if os.path.isfile(target):
        with open(target) as f:
            doc = json.load(f)
        return _render_slo_doc(target, doc)
    tsdb = _load_obs_module("tsdb")
    slo = _load_obs_module("slo")
    drift = _load_obs_module("drift")
    store = tsdb.SeriesStore.from_run_dir(target)
    lines = [f"== SLO report: {target} =="]
    if not store.samples:
        lines.append(
            "no tsdb samples found (expected host-<k>/tsdb/seg-*."
            "jsonl — is observability.tsdb on and the run flushed?)")
        return "\n".join(lines)
    t0, t1 = store.time_range()
    lines.append(f"{len(store.samples)} sample(s) over "
                 f"{t1 - t0:.1f}s; {len(store.counter_keys(''))} "
                 f"counter / {len(store.gauge_keys(''))} gauge series")
    spec = _find_slo_spec(target, spec_path)
    if spec is None:
        lines.append("no SLO spec (--slo-spec slo.yaml) — rendering "
                     "drift only")
        objectives = []
    else:
        objectives = slo.load_slo_yaml(spec)
        lines.append(f"spec: {spec} ({len(objectives)} objective(s))")
    if objectives:
        engine = slo.SloEngine(objectives)
        times = sorted({s["t"] for s in store.samples})
        history: Dict[str, List] = {}
        for t in times:
            for st in engine.evaluate(store, now=t):
                history.setdefault(st.slo_key, []).append(st)
        for key in sorted(history):
            sts = history[key]
            last = sts[-1]
            lines += ["", f"objective {key} [{last.detail}] "
                      f"target {last.target:.2%}:"]
            lines.append(
                f"  now: alert={last.alert}  budget_remaining="
                f"{last.budget_remaining:.2f}  bad_fraction="
                f"{last.bad_fraction:.2%}")
            rows = [[w, f"{b['long']:.2f}", f"{b['short']:.2f}"]
                    for w, b in sorted(last.burn.items())]
            lines.append(_table(rows, ["window", "burn(long)",
                                       "burn(short)"]))
            budgets = [s.budget_remaining for s in sts]
            lines.append(f"  budget timeline [{min(budgets):.2f}.."
                         f"{max(budgets):.2f}]: "
                         f"{_spark(budgets)}")
            trans = engine.transitions(last.name, last.group)
            if trans:
                lines.append("  transitions: " + "  ".join(
                    f"+{t - t0:.1f}s->{lvl}" for t, lvl in trans))
    callouts = drift.drift_report(store, [""])
    drifting = [c for c in callouts if c["drifting"]]
    lines += ["", f"drift: {len(drifting)} of {len(callouts)} "
              f"series flagged (score >= 1.0 at peak)"]
    for c in (drifting or callouts[:3]):
        peak_off = (f"+{c['peak_at'] - t0:.1f}s"
                    if c.get("peak_at") is not None else "-")
        lines.append(
            f"  {'DRIFT ' if c['drifting'] else ''}{c['series']}: "
            f"peak {c['peak_score']:.2f} at {peak_off} "
            f"(last {c['score']:.2f}, {c['points']} pts)")
    return "\n".join(lines)


def _render_slo_doc(label: str, doc: Dict) -> str:
    """Render a ``zoo-loadtest --slo-out`` document."""
    lines = [f"== SLO report: {label} "
             f"(scenario {doc.get('scenario', '?')}) =="]
    for c in doc.get("checks", []):
        mark = "ok  " if c.get("passed") else "FAIL"
        lines.append(f"  [{mark}] {c.get('name')}: {c.get('detail')}")
    timeline = doc.get("timeline") or []
    if timeline:
        by_key: Dict[str, List[Dict]] = {}
        for row in timeline:
            for st in row:
                key = st.get("name", "?")
                if st.get("group"):
                    key += f"/{st['group']}"
                by_key.setdefault(key, []).append(st)
        for key in sorted(by_key):
            sts = by_key[key]
            budgets = [s.get("budget_remaining", 0.0) for s in sts]
            worst = max(sts, key=lambda s: {"ok": 0, "warn": 1,
                                            "page": 2}.get(
                                                s.get("alert"), 0))
            lines.append(
                f"  {key}: worst alert={worst.get('alert')}  budget "
                f"[{min(budgets):.2f}..{max(budgets):.2f}] "
                f"{_spark(budgets)}")
    return "\n".join(lines)


# ------------------------------------------------------------ multi-host
def _load_aggregator_module():
    """Load observability/aggregator.py by FILE PATH (not package
    import): the module is stdlib-only by contract, so the merge works
    on machines without jax installed."""
    import importlib.util
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "analytics_zoo_tpu", "observability", "aggregator.py")
    spec = importlib.util.spec_from_file_location("_zoo_aggregator",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    # register before exec: modules the aggregator itself path-loads
    # (reqtrace.py) define dataclasses, whose field-annotation
    # resolution needs the defining module present in sys.modules
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _load_batchjobs_report_module():
    """Load batchjobs/report.py (stdlib-only by contract) as a
    synthetic package by file path — same jax-free trick as the
    aggregator loader, but with a package shell so the module's
    relative imports (spec.py, manifest.py) resolve."""
    import importlib.util
    import types
    pkg_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "analytics_zoo_tpu", "batchjobs")
    name = "_zoo_batchjobs"
    if name + ".report" in sys.modules:
        return sys.modules[name + ".report"]
    pkg = types.ModuleType(name)
    pkg.__path__ = [pkg_dir]
    sys.modules[name] = pkg
    spec = importlib.util.spec_from_file_location(
        name + ".report", os.path.join(pkg_dir, "report.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def render_job_report(run_dir: str) -> str:
    """The --job section: shard progress table + capacity/cost report
    from the job ledger, then the fleet's batch_* counters and the
    per-host straggler callout joined from the merged host snapshots
    (when the workers left any)."""
    batch = _load_batchjobs_report_module()
    lines = [f"== batch job report: {run_dir} ==", "",
             batch.render_job_section(run_dir)]
    try:
        agg = _load_aggregator_module()
        aggregator = agg.ClusterAggregator.from_run_dir(run_dir,
                                                        offline=True)
        host_snaps, merged = aggregator.cluster_view()
    except Exception:
        host_snaps, merged = {}, None
    if host_snaps and merged:
        counters = {k: v for k, v in
                    merged.get("counters", {}).items()
                    if k.startswith("batch_")}
        if counters:
            lines += ["", "fleet batch counters (merged over "
                      f"{len(host_snaps)} host snapshot(s)):"]
            for k in sorted(counters):
                lines.append(f"  {k} = {counters[k]:g}")
        cluster = merged.get("cluster", {})
        if cluster.get("straggler"):
            lines.append(
                f"  STRAGGLER (step-time skew): "
                f"{cluster['straggler']} "
                f"(+{cluster.get('skew_fraction', 0.0):.0%} vs "
                f"median)")
    return "\n".join(lines)


def _fmt_bytes(v: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(v) < 1024.0 or unit == "TiB":
            return f"{v:.1f}{unit}" if unit != "B" else f"{v:.0f}B"
        v /= 1024.0
    return f"{v:.1f}TiB"


def render_cluster_report(run_dir: str, agg_mod=None,
                          merged_trace_out: Optional[str] = None
                          ) -> Tuple[str, Dict]:
    """The fleet-level report: skew table, straggler, bubbles,
    collectives, cluster totals.  Returns (text, merged_snapshot)."""
    agg = agg_mod if agg_mod is not None else _load_aggregator_module()
    # offline by definition: a finished run's recorded ports may have
    # been reused by unrelated processes — never scrape them here
    aggregator = agg.ClusterAggregator.from_run_dir(run_dir,
                                                    offline=True)
    # the same collect/merge/attribute path the live /metrics/cluster
    # serves, so the offline report and the endpoint can never
    # disagree about skew gauges or missing-host accounting
    host_snaps, merged = aggregator.cluster_view()
    if not host_snaps:
        raise SystemExit(
            f"{run_dir}: no worker snapshots found (expected "
            f"host-<k>/metrics.jsonl slots — launch with "
            f"zoo-launch --run-dir)")
    report = merged["cluster"]
    # persist the federated snapshot so a later run can gate against
    # it: obs_report.py --merge-hosts RUN_B --diff RUN_A/cluster_
    # snapshot.json compares cluster views, not one host vs four
    snap_path = os.path.join(run_dir, "cluster_snapshot.json")
    try:
        with open(snap_path, "w") as f:
            json.dump(merged, f, indent=2)
    except OSError:
        snap_path = None

    lines = [f"== cluster report: {run_dir} "
             f"({len(host_snaps)} hosts) =="]
    missing = report.get("missing_hosts")
    if missing:
        lines.append(
            f"MISSING: {len(missing)} of {report['expected_hosts']} "
            f"workers left no snapshot (crashed before first flush?): "
            f"{missing}")

    # ---- per-host step-time skew ----------------------------------
    rows = []
    for host in sorted(report["per_host"]):
        d = report["per_host"][host]
        rows.append([
            host, d["steps"], _fmt_seconds(d["mean_step_s"]),
            _fmt_seconds(d["p50_step_s"]),
            _fmt_seconds(d["mean_barrier_wait_s"])])
    if rows:
        lines += ["", "per-host step time (barrier wait ~0 on the "
                  "straggler, ~skew on the fastest host):",
                  _table(rows, ["host", "steps", "mean", "p50",
                                "barrier wait"])]
    if report.get("straggler"):
        lines.append(
            f"STRAGGLER: {report['straggler']} "
            f"(+{report['skew_fraction']:.0%} vs median step time, "
            f"skew {_fmt_seconds(report['skew_seconds'])})")
    elif len(host_snaps) >= 2:
        lines.append(
            f"no straggler beyond threshold (max-median skew "
            f"{_fmt_seconds(report.get('skew_seconds', 0.0))}, "
            f"{report.get('skew_fraction', 0.0):+.0%})")

    # ---- pipeline / collectives -----------------------------------
    bubble = report.get("pipeline_bubble_fraction")
    if bubble is not None:
        lines.append(f"pipeline bubble fraction: {bubble:.2f} "
                     f"(P-1 of M+P-1 ticks idle — raise "
                     f"num_microbatches to amortize)")
    coll = report.get("collectives")
    if coll:
        rows = []
        for op in sorted(coll):
            d = coll[op]
            secs = _fmt_seconds(d["seconds"]) if d["seconds"] else "-"
            rows.append([op, _fmt_bytes(d["bytes"]), secs])
        lines += ["", "collectives (estimated from sharding specs; "
                  "time needs observability.ici_gbps):",
                  _table(rows, ["op", "bytes", "est time"])]

    # ---- cluster-summed counters ----------------------------------
    totals = [(k, v) for k, v in sorted(merged["counters"].items())
              if v]
    if totals:
        rows = [[k, f"{v:.6g}"] for k, v in totals[:20]]
        lines += ["", "cluster totals (counters summed across hosts):",
                  _table(rows, ["counter", "total"])]
        if len(totals) > 20:
            lines.append(f"... and {len(totals) - 20} more")

    # ---- merged trace ---------------------------------------------
    out_path = merged_trace_out or os.path.join(run_dir,
                                                "merged_trace.json")
    try:
        merged_trace = agg.merge_traces(run_dir, out_path)
        n_ev = len(merged_trace.get("traceEvents", []))
        if n_ev:
            lines.append("")
            lines.append(
                f"merged trace: {out_path} ({n_ev} events, "
                f"{merged_trace['otherData']['hosts_merged']} hosts, "
                f"aligned on the launcher clock anchor — open in "
                f"https://ui.perfetto.dev)")
    except Exception as e:   # traces are optional artifacts
        lines.append(f"(trace merge skipped: {e})")
    if snap_path:
        lines.append(f"cluster snapshot: {snap_path} (gate a later "
                     f"run with --merge-hosts RUN --diff {snap_path})")
    return "\n".join(lines), merged


# ----------------------------------------------------------------- diff
# (metric selector, direction) pairs the diff gates on; "up" = higher
# is better (regression when it drops), "down" = lower is better
_DIFF_KEYS = [
    ("gauge", "train_throughput_samples_per_sec", "up"),
    ("gauge", "train_mfu", "up"),
    ("hist_p50", "train_step_latency_seconds", "down"),
    ("hist_p50", "train_step_time_seconds", "down"),
    ("hist_p50", "serving_request_latency_seconds", "down"),
    ("hist_p50", "data_batch_wait_seconds", "down"),
]


def _diff_values(snap: Dict, kind: str, name: str
                 ) -> List[Tuple[str, float]]:
    if kind == "gauge":
        return [(lab, float(v))
                for lab, v in _labeled(snap.get("gauges", {}), name)]
    return [(lab, float(h["p50"]))
            for lab, h in _labeled(snap.get("histograms", {}), name)
            if h.get("count")]


def render_diff(cur_label: str, cur: Dict, base_label: str, base: Dict,
                threshold: float) -> Tuple[str, int]:
    lines = [f"== diff: {cur_label} vs baseline {base_label} "
             f"(threshold {threshold:.0%}) =="]
    regressions = 0
    for kind, name, direction in _DIFF_KEYS:
        base_vals = dict(_diff_values(base, kind, name))
        for lab, cur_v in _diff_values(cur, kind, name):
            base_v = base_vals.get(lab)
            if base_v is None or base_v <= 0 or cur_v <= 0:
                continue
            change = cur_v / base_v - 1.0
            worse = change < -threshold if direction == "up" \
                else change > threshold
            mark = "  REGRESSION" if worse else ""
            regressions += bool(worse)
            disp = f"{name}{{{lab}}}" if lab else name
            if kind != "gauge":
                disp += " p50"
            lines.append(f"{disp}: {base_v:.6g} -> {cur_v:.6g} "
                         f"({change:+.1%}){mark}")
    if regressions:
        lines.append(f"{regressions} regression(s) beyond "
                     f"{threshold:.0%}")
    else:
        lines.append("no regressions beyond threshold")
    return "\n".join(lines), (1 if regressions else 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="render a registry snapshot (+ optional Chrome "
                    "trace) into a training-health report; --diff "
                    "gates on regressions; --merge-hosts federates a "
                    "multi-host run directory")
    ap.add_argument("snapshot", nargs="?", default=None,
                    help="registry JSONL / bench_metrics"
                         ".json / snapshot JSON")
    ap.add_argument("--trace", default=None,
                    help="Chrome-trace JSON (Tracer.export_chrome_"
                         "trace or /trace)")
    ap.add_argument("--workload", default=None,
                    help="bench_metrics.json: report only this "
                         "workload")
    ap.add_argument("--diff", metavar="BASELINE", default=None,
                    help="compare against a baseline snapshot; exit 1 "
                         "on regression")
    ap.add_argument("--threshold", type=float, default=0.10)
    ap.add_argument("--merge-hosts", metavar="RUN_DIR", default=None,
                    help="launcher run directory (host-<k>/ slots): "
                         "render the cluster skew/straggler report, "
                         "merge the per-host traces, then report the "
                         "federated snapshot")
    ap.add_argument("--merged-trace-out", default=None,
                    help="where --merge-hosts writes the merged "
                         "Chrome trace (default "
                         "RUN_DIR/merged_trace.json)")
    ap.add_argument("--requests", metavar="RUN_DIR_OR_FILE",
                    default=None,
                    help="render the slowest-request station "
                         "waterfall from requests.json timelines: a "
                         "single requests.json, or a run directory "
                         "whose host-<k>/requests.json are merged "
                         "(partial timelines sharing a trace_id are "
                         "joined)")
    ap.add_argument("--slowest", type=int, default=10,
                    help="--requests: how many of the slowest "
                         "requests to waterfall (default 10)")
    ap.add_argument("--job", metavar="RUN_DIR", default=None,
                    help="batch job run directory (zoo-batch): render "
                         "the shard progress table, capacity/cost "
                         "report and per-host straggler callout from "
                         "the job ledger + merged host snapshots")
    ap.add_argument("--slo", metavar="RUN_DIR_OR_FILE", default=None,
                    help="render error-budget timelines, burn-rate "
                         "tables and drift callouts from a run dir's "
                         "tsdb segments (host-<k>/tsdb/), or a "
                         "slo_report.json from zoo-loadtest --slo-out")
    ap.add_argument("--slo-spec", metavar="SLO_YAML", default=None,
                    help="--slo: SLO objective spec file (default: "
                         "<run_dir>/slo.yaml, then the repo slo.yaml)")
    ap.add_argument("--incident", metavar="RUN_DIR_OR_FILE",
                    default=None,
                    help="render zoo-doctor's incident timeline + "
                         "ranked root-cause hypotheses from a run "
                         "dir's forensic artifacts (reuses an "
                         "existing incident.json when present), or "
                         "from an incident.json file directly")
    args = ap.parse_args(argv)

    if args.merge_hosts is None and args.snapshot is None \
            and args.requests is None and args.job is None \
            and args.slo is None and args.incident is None:
        ap.error("need a snapshot file, --merge-hosts RUN_DIR, "
                 "--requests RUN_DIR, --job RUN_DIR, --slo RUN_DIR, "
                 "or --incident RUN_DIR")

    if args.incident:
        print(render_incident_report(args.incident))
        print()
        if args.merge_hosts is None and args.snapshot is None \
                and args.requests is None and args.job is None \
                and args.slo is None:
            return 0

    if args.slo:
        print(render_slo_report(args.slo, args.slo_spec))
        print()
        if args.merge_hosts is None and args.snapshot is None \
                and args.requests is None and args.job is None:
            return 0

    if args.job:
        print(render_job_report(args.job))
        print()
        if args.merge_hosts is None and args.snapshot is None \
                and args.requests is None:
            return 0

    if args.requests:
        agg = _load_aggregator_module()
        merged_reqs = agg.merge_requests(args.requests)
        print(render_requests_report(args.requests, merged_reqs,
                                     top=args.slowest))
        print()
        if args.merge_hosts is None and args.snapshot is None:
            return 0

    if args.merge_hosts:
        text, merged = render_cluster_report(
            args.merge_hosts, merged_trace_out=args.merged_trace_out)
        print(text)
        print()
        # the federated snapshot then flows through the standard
        # report (and --diff, e.g. against a previous run's merge)
        snaps = [("cluster", merged)]
        if args.snapshot:
            snaps += load_snapshots(args.snapshot, args.workload)
    elif (doc := _peek_loadtest(args.snapshot)) is not None:
        # a zoo-loadtest report: verdict + capacity table first, then
        # the embedded registry snapshot through the standard report
        print(render_loadtest_report(args.snapshot, doc))
        print()
        snaps = ([(args.snapshot, doc["metrics"])]
                 if _is_snapshot(doc.get("metrics")) else [])
    else:
        snaps = load_snapshots(args.snapshot, args.workload)
    trace_events = None
    if args.trace:
        with open(args.trace) as f:
            doc = json.load(f)
        trace_events = doc.get("traceEvents", doc) \
            if isinstance(doc, dict) else doc

    rc = 0
    for label, snap in snaps:
        print(render_report(label, snap, trace_events))
        print()
    if args.diff:
        base = load_snapshots(args.diff, args.workload)
        # pair snapshots by label (multi-workload bench_metrics.json:
        # EVERY shared workload gates, a regression in any of them
        # fails); fall back to first-vs-first when labels don't
        # overlap (plain files, whose label is their path)
        base_map = dict(base)
        pairs = [(lab, snap, lab, base_map[lab])
                 for lab, snap in snaps if lab in base_map]
        if not pairs:
            pairs = [(snaps[0][0], snaps[0][1], base[0][0], base[0][1])]
        missing = [lab for lab, _ in snaps
                   if base_map and lab not in base_map and len(base) > 1]
        for cur_label, cur, base_label, base_snap in pairs:
            text, r = render_diff(cur_label, cur, base_label,
                                  base_snap, args.threshold)
            print(text)
            rc = max(rc, r)
        if missing:
            print(f"not in baseline (not gated): {missing}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
