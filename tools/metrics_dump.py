"""Render a JSONL metrics file (or a live /varz endpoint) as a
latency/throughput summary table.

Reads the output of ``analytics_zoo_tpu.metrics.exporters.write_jsonl``
(one registry snapshot per line), or scrapes one snapshot from a running
process's ``/varz`` endpoint (``MetricsServer``, ZOO_METRICS_PORT), and
prints, for the LATEST snapshot:

- histograms: count, mean, p50/p95/p99 (seconds-named metrics shown in
  ms);
- counters/gauges: the value, plus the delta and rate against the FIRST
  snapshot in the file when more than one line is present (file mode
  only — a single live scrape has no baseline).

Metric families worth a `--prefix` of their own: `zoo_train` (fit-loop
breakdown; under ``ZOO_STEPS_PER_DISPATCH=K`` one histogram observation
covers a K-step fused dispatch while the steps/records counters keep
counting real steps), `zoo_serving`, `zoo_inference`,
`zoo_data_prefetch` (host data plane), `zoo_compile` (the compile
plane: `zoo_compile_seconds{label=...}` per AOT compile plus the
`zoo_compile_cache_hits_total` / `zoo_compile_cache_misses_total` pair
that splits cold from ``ZOO_COMPILE_CACHE``-warm starts), and
`zoo_hlo` (the HLO graph lint's analytic cost features per compiled
program: `zoo_hlo_flops` / `zoo_hlo_bytes_accessed` /
`zoo_hlo_collectives` / `zoo_hlo_collective_bytes` /
`zoo_hlo_fused_dispatches` / `zoo_hlo_ops` / `zoo_hlo_findings`, all
`{label=<compile label>}`, plus `zoo_hlo_lint_findings_total{rule=}`
— see docs/static-analysis.md), `zoo_autotune` (the closed-loop
controller's current worker/depth/read-ahead/K gauges, RAM
budget/estimate pair, and `zoo_autotune_decisions_total{knob,reason}`),
and `zoo_fleet` (the serving fleet's live/target replica gauges,
`zoo_fleet_decisions_total{action,reason}`, the exactly-once
fault-tolerance pair `zoo_fleet_lease_takeovers_total` /
`zoo_fleet_replica_deaths_total`, the scaler's
`zoo_fleet_est_p99_seconds` / `zoo_fleet_unclaimed_backlog` window
signals, and `zoo_fleet_batch_flushes_total{reason}` from the
continuous batcher), `zoo_router` (the multi-tenant serving plane,
serving/router.py: `zoo_router_models`,
`zoo_router_decisions_total{model,action}` and the per-model
`zoo_fleet_model_replicas` / `zoo_fleet_model_backlog` /
`zoo_fleet_model_est_p99_seconds` gauges), `zoo_admission` (the
front-door shedding plane, serving/admission.py:
`zoo_admission_requests_total{model,verdict}`,
`zoo_admission_state{model}`,
`zoo_admission_retry_after_seconds{model}` and
`zoo_admission_evaluations_total`), and `zoo_oracle` (the predictive
compile plane,
analysis/oracle.py: `zoo_oracle_predictions_total{consumer}`,
`zoo_oracle_predicted_steps_per_sec{config}` /
`zoo_oracle_measured_steps_per_sec{config}` /
`zoo_oracle_rel_error{config}` per scored config, and
`zoo_oracle_fit_samples` — the residual model's training-set size, 0
while the oracle is analytic-only), `zoo_scrape` (the zoowatch
federation tier, metrics/scrape.py: `zoo_scrape_targets`,
per-target `zoo_scrape_fetches_total` / `zoo_scrape_errors_total` /
`zoo_scrape_staleness_seconds`, and the `zoo_scrape_fetch_seconds`
pull-latency histogram), and `zoo_slo` (the burn-rate engine,
metrics/slo.py: `zoo_slo_burn_rate{slo,window}` for the short/long
alert windows, `zoo_slo_alert_active{slo}`, `zoo_slo_alerts_total`
and `zoo_slo_evaluations_total`), and `zoo_kernel` (the Pallas kernel
plane, parallel/plan.py kernel_rules + ops/pallas:
`zoo_kernel_selections{label,scope,kernel}` — what the fifth rule
table resolved per compile label,
`zoo_kernel_invocations{kernel,backend}` — pallas vs fallback routing
counts, and the bytes loop
`zoo_kernel_measured_bytes{label}` /
`zoo_kernel_predicted_bytes{label}` /
`zoo_kernel_bytes_rel_error{label}` — measured custom-call HBM bytes
against costmodel.kernel_bytes; the HLO side is
`zoo_hlo_custom_kernels{label}` / `zoo_hlo_custom_kernel_bytes{label}`
under the `zoo_hlo` family).  When the scraped ``/varz`` carries
a structured decision log (``autotune`` / ``fleet`` / ``router`` /
``admission`` / ``oracle`` / ``elastic`` / ``scrape`` / ``slo``
sections), it is additionally
rendered as a table — time, knob/action, old → new, reason; predicted
vs measured per config; per-target scrape health; firing SLO alerts
with their short/long burn rates — above the metric rows.

Usage:
  python tools/metrics_dump.py METRICS.jsonl [--prefix zoo_serving]
  python tools/metrics_dump.py METRICS.jsonl --prefix zoo_compile
  python tools/metrics_dump.py --url host:9090 --prefix zoo_hlo
  python tools/metrics_dump.py METRICS.jsonl --prometheus   # re-render
  python tools/metrics_dump.py --url http://host:9090/varz
  python tools/metrics_dump.py --url host:9090   # /varz implied
  python tools/metrics_dump.py --url host:9090 --watch 2   # live panel
"""

import argparse
import json
import sys


def load(path):
    docs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                docs.append(json.loads(line))
            except json.JSONDecodeError:
                print(f"warning: skipping unparseable line", file=sys.stderr)
    if not docs:
        raise SystemExit(f"{path}: no snapshots found")
    return docs


def fetch(url):
    """One live /varz snapshot as a single-doc list (the same downstream
    shape as a one-line JSONL file).  Accepts ``host:port`` shorthand
    and a bare server root; ``/varz`` is implied."""
    import urllib.request

    if "://" not in url:
        url = "http://" + url
    if not url.rstrip("/").endswith("/varz"):
        url = url.rstrip("/") + "/varz"
    try:
        with urllib.request.urlopen(url, timeout=10) as r:
            doc = json.load(r)
    except (OSError, json.JSONDecodeError) as e:
        raise SystemExit(f"{url}: scrape failed: {e}")
    if "samples" not in doc:
        raise SystemExit(f"{url}: no samples in response — not a "
                         "MetricsServer /varz endpoint?")
    return [doc]


def _key(sample):
    try:
        from analytics_zoo_tpu.metrics import sample_key
    except ModuleNotFoundError:
        # standalone invocation (`python tools/metrics_dump.py ...`) puts
        # tools/ on sys.path, not the repo root: fall back to the same
        # canonical shape so the tool works without an installed package
        labels = sample.get("labels")
        if not labels:
            return sample["name"]
        inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
        return f"{sample['name']}{{{inner}}}"
    return sample_key(sample)


def _scale(name, value):
    """seconds-named metrics print in ms — latencies live there."""
    if name.endswith("_seconds") or "_seconds{" in name:
        return value * 1e3, "ms"
    return value, ""


def render_autotune(doc, prefix="", out=None):
    """Decision table for the ``autotune`` section a live ``/varz``
    carries when a closed-loop controller ran (feature/autotune.py):
    one row per knob change (time, knob, old→new, reason), plus each
    controller's current config.  Skipped when the snapshot has no
    autotune section or ``--prefix`` filters it out."""
    import datetime

    auto = doc.get("autotune")
    if not auto or (prefix and not "zoo_autotune".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for ctl in auto.get("controllers", []):
        cur = ctl.get("current", {})
        emit("\nautotune: workers={workers} depth={depth} "
             "read_ahead={read_ahead} K={k} (settled={k_settled})".format(
                 **{k: cur.get(k) for k in
                    ("workers", "depth", "read_ahead", "k", "k_settled")}))
    decisions = auto.get("decisions", [])
    if decisions:
        emit(f"\n{'time':<14}{'knob':<12}{'change':<14}reason")
        for d in decisions:
            t = datetime.datetime.fromtimestamp(d["ts"]).strftime(
                "%H:%M:%S.%f")[:-3]
            emit(f"{t:<14}{d['knob']:<12}"
                 f"{str(d['old']) + ' -> ' + str(d['new']):<14}"
                 f"{d['reason']}")


def render_fleet(doc, prefix="", out=None):
    """Fleet panel for the ``fleet`` section a live ``/varz`` carries
    when a FleetController ran (serving/fleet.py): each controller's
    replica/scaler state, then one row per scale decision (time, action,
    replicas old→new, estimated p99 vs the window's queue, reason).
    Skipped when the snapshot has no fleet section or ``--prefix``
    filters it out."""
    import datetime

    fleet = doc.get("fleet")
    if not fleet or (prefix and not "zoo_fleet".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for ctl in fleet.get("controllers", []):
        cur = ctl.get("current", {})
        win = cur.get("window", {})
        emit("\nfleet: replicas={replicas}/{target} (max={max_replicas}) "
             "slo_p99={slo_p99_ms}ms mode={mode}".format(
                 **{k: cur.get(k) for k in
                    ("replicas", "target", "max_replicas", "slo_p99_ms",
                     "mode")}))
        emit("  window: predict_p99={predict_p99_ms}ms "
             "rate={service_rate}/s queue={queue_depth} "
             "mem={memory_ratio}".format(
                 **{k: win.get(k) for k in
                    ("predict_p99_ms", "service_rate", "queue_depth",
                     "memory_ratio")}))
    decisions = fleet.get("decisions", [])
    if decisions:
        emit(f"\n{'time':<14}{'action':<9}{'replicas':<11}"
             f"{'est_p99':<11}{'queue':<7}reason")
        for d in decisions:
            t = datetime.datetime.fromtimestamp(d["ts"]).strftime(
                "%H:%M:%S.%f")[:-3]
            est = "-" if d.get("est_p99_ms") is None \
                else f"{d['est_p99_ms']:.0f} ms"
            emit(f"{t:<14}{d['action']:<9}"
                 f"{str(d['old']) + ' -> ' + str(d['new']):<11}"
                 f"{est:<11}{str(d.get('queue_depth', '-')):<7}"
                 f"{d['reason']}")


def render_router(doc, prefix="", out=None):
    """Router panel for the ``router`` section a live ``/varz`` carries
    when a ModelRouter ran (serving/router.py): each router's per-model
    state (stream, replicas, backlog, the oracle verdict's pad buckets
    and batch budget, the admission verdict), then one row per
    prime/scale/stop decision.  Skipped when the snapshot has no router
    section or ``--prefix`` filters it out."""
    import datetime

    router = doc.get("router")
    if not router or (prefix and not "zoo_router".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for r in router.get("routers", []):
        cur = r.get("current", {})
        emit("\nrouter: admission={admission} mode={mode}".format(
            **{k: cur.get(k) for k in ("admission", "mode")}))
        models = cur.get("models", {})
        if models:
            emit(f"  {'model':<12}{'replicas':>9}{'backlog':>9}"
                 f"{'slo_p99':>9}{'buckets':<16}{'budget':>9}  admission")
            for name in sorted(models):
                m = models[name]
                verdict = m.get("verdict") or {}
                adm = m.get("admission") or {}
                buckets = verdict.get("pad_buckets")
                budget = verdict.get("batch_budget_ms")
                emit(f"  {name:<12}{m.get('replicas', 0):>9}"
                     f"{m.get('backlog', 0):>9}"
                     f"{m.get('spec', {}).get('slo_p99_ms', 0):>8g}m"
                     f" {str(buckets or '-'):<15}"
                     f"{('-' if budget is None else f'{budget:.1f}ms'):>9}"
                     f"  {adm.get('state', '-')}")
    decisions = router.get("decisions", [])
    if decisions:
        emit(f"\n  {'time':<14}{'model':<12}{'action':<8}detail")
        for d in decisions:
            t = datetime.datetime.fromtimestamp(d["ts"]).strftime(
                "%H:%M:%S.%f")[:-3]
            if d.get("action") == "scale":
                detail = (f"{d.get('old')} -> {d.get('new')} "
                          f"backlog={d.get('backlog')}")
            else:
                detail = (f"replicas={d.get('replicas')} "
                          f"buckets={d.get('pad_buckets')} "
                          f"budget={d.get('batch_budget_ms')}")
            emit(f"  {t:<14}{d.get('model', '?'):<12}"
                 f"{d.get('action', '?'):<8}{detail}")


def render_admission(doc, prefix="", out=None):
    """Admission panel for the ``admission`` section a live ``/varz``
    carries when an AdmissionController ran (serving/admission.py):
    each controller's current verdict (state, reason, retry-after, the
    observed drain rate), then one row per accept/shed transition.
    Skipped when the snapshot has no admission section or ``--prefix``
    filters it out."""
    import datetime

    admission = doc.get("admission")
    if not admission or (prefix
                         and not "zoo_admission".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for ctl in admission.get("controllers", []):
        cur = ctl.get("current", {})
        emit("\nadmission: model={model} stream={stream} state={state} "
             "retry_after={retry_after_ms}ms backlog_limit="
             "{backlog_limit} drain={drain_rate}/s".format(
                 **{k: cur.get(k) for k in
                    ("model", "stream", "state", "retry_after_ms",
                     "backlog_limit", "drain_rate")}))
    decisions = admission.get("decisions", [])
    if decisions:
        emit(f"\n  {'time':<14}{'model':<12}{'state':<8}"
             f"{'retry_after':>12}{'backlog':>9}  reason")
        for d in decisions:
            t = datetime.datetime.fromtimestamp(d["ts"]).strftime(
                "%H:%M:%S.%f")[:-3]
            emit(f"  {t:<14}{d.get('model', '?'):<12}"
                 f"{d.get('state', '?'):<8}"
                 f"{d.get('retry_after_ms', 0):>10.0f}ms"
                 f"{d.get('backlog', 0):>9}  {d.get('reason', '')}")


def render_oracle(doc, prefix="", out=None):
    """Predicted-vs-measured panel for the ``oracle`` section a live
    ``/varz`` carries when a ConfigOracle ran (analysis/oracle.py):
    each oracle's peak-table source and residual-fit size, then one row
    per scored config — time, consumer, config, predicted and measured
    steps/sec, relative error ("-" while the outcome is still open).
    Skipped when the snapshot has no oracle section or ``--prefix``
    filters it out."""
    import datetime

    oracle = doc.get("oracle")
    if not oracle or (prefix and not "zoo_oracle".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for o in oracle.get("oracles", []):
        peaks = o.get("peaks", {})
        emit("\noracle: peaks={source} fit_samples={n} "
             "residual_ready={ready}".format(
                 source=peaks.get("source"), n=o.get("fit_samples"),
                 ready=o.get("residual_ready")))
    predictions = oracle.get("predictions", [])
    if predictions:
        emit(f"\n{'time':<14}{'consumer':<12}{'config':<14}"
             f"{'predicted/s':>12}{'measured/s':>12}{'rel_err':>9}")
        for p in predictions:
            t = datetime.datetime.fromtimestamp(p["ts"]).strftime(
                "%H:%M:%S.%f")[:-3]
            meas = p.get("measured_steps_per_sec")
            err = p.get("rel_error")
            chosen = "*" if p.get("chosen") else " "
            emit(f"{t:<14}{p['consumer']:<12}"
                 f"{chosen + p['config']:<14}"
                 f"{p['predicted_steps_per_sec']:>12.1f}"
                 f"{('-' if meas is None else f'{meas:.1f}'):>12}"
                 f"{('-' if err is None else f'{err:.3f}'):>9}")


def render_elastic(doc, prefix="", out=None):
    """Elastic panel for the ``elastic`` section a live ``/varz``
    carries when a TrainSupervisor ran (elastic/supervisor.py): each
    supervisor's generation/world/cohort state, the member table with
    per-worker micro-batch shares, then one row per rejoin decision
    (time, action, generation, world, reason).  Skipped when the
    snapshot has no elastic section or ``--prefix`` filters it out."""
    import datetime

    elastic = doc.get("elastic")
    if not elastic or (prefix and not "zoo_elastic".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for sup in elastic.get("supervisors", []):
        cur = sup.get("current", {})
        emit("\nelastic: generation={generation} "
             "world={world}/{target_workers} (min={min_workers}) "
             "mesh={mesh} plan={plan} k={k} chief={chief} "
             "repicks={repicks}".format(
                 **{k: cur.get(k) for k in
                    ("generation", "world", "target_workers",
                     "min_workers", "mesh", "plan", "k", "chief",
                     "repicks")}))
        members = cur.get("members", [])
        if members:
            shares = cur.get("shares", {})
            workers = cur.get("workers", {})
            emit(f"  {'member':<8}{'share':>6}  {'pid':>8}  alive")
            for w in members:
                info = workers.get(w, {})
                emit(f"  {w:<8}{str(shares.get(w, '-')):>6}  "
                     f"{str(info.get('pid', '-')):>8}  "
                     f"{info.get('alive', '-')}")
    decisions = elastic.get("decisions", [])
    if decisions:
        emit(f"\n{'time':<14}{'action':<10}{'gen':<5}{'world':<7}"
             f"{'worker':<8}reason")
        for d in decisions:
            t = datetime.datetime.fromtimestamp(d["ts"]).strftime(
                "%H:%M:%S.%f")[:-3]
            emit(f"{t:<14}{d['action']:<10}"
                 f"{str(d.get('generation', '-')):<5}"
                 f"{str(d.get('world', '-')):<7}"
                 f"{str(d.get('worker', '-')):<8}"
                 f"{d['reason']}")


def render_scrape(doc, prefix="", out=None):
    """Federation panel for the ``scrape`` section a live ``/varz``
    carries when a VarzScraper ran (metrics/scrape.py): one row per
    scraped target — health, staleness age, fetch/error counts, last
    error.  Skipped when the snapshot has no scrape section or
    ``--prefix`` filters it out."""
    scrapers = doc.get("scrape")
    if not scrapers or (prefix and not "zoo_scrape".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for s in scrapers:
        emit("\nscrape: healthy={healthy} interval={interval}s "
             "stale_after={stale_after}s".format(
                 **{k: s.get(k) for k in
                    ("healthy", "interval", "stale_after")}))
        targets = s.get("targets", {})
        if targets:
            emit(f"  {'target':<12}{'ok':<6}{'age':>8}{'fetches':>9}"
                 f"{'errors':>8}  last_error")
            for name in sorted(targets):
                t = targets[name]
                age = t.get("age_seconds")
                emit(f"  {name:<12}{str(t.get('healthy')):<6}"
                     f"{('-' if age is None else f'{age:.1f}s'):>8}"
                     f"{t.get('fetches', 0):>9}{t.get('errors', 0):>8}"
                     f"  {t.get('last_error') or '-'}")


def render_slo(doc, prefix="", out=None):
    """SLO/alert panel for the ``slo`` section a live ``/varz`` carries
    when an SloEngine ran (metrics/slo.py): each engine's specs with
    their objectives and windows, any alerts with short/long burn rates
    (firing alerts marked ``*``), then one row per decision-log entry.
    Skipped when the snapshot has no slo section or ``--prefix``
    filters it out."""
    import datetime

    engines = doc.get("slo")
    if not engines or (prefix and not "zoo_slo".startswith(prefix)):
        return
    emit = print if out is None else (lambda s: out.append(s))
    for eng in engines:
        specs = eng.get("specs", [])
        if specs:
            emit(f"\nslo: {'name':<24}{'family':<34}{'objective':>10}"
                 f"{'threshold':>11}{'windows':>12}")
            for sp in specs:
                win = (f"{sp.get('short_window'):g}/"
                       f"{sp.get('long_window'):g}s")
                emit(f"     {sp.get('name', '?'):<24}"
                     f"{sp.get('family', '?'):<34}"
                     f"{sp.get('objective'):>10g}"
                     f"{sp.get('threshold'):>11g}{win:>12}")
        alerts = eng.get("alerts", [])
        if alerts:
            emit(f"\n  {'alert':<25}{'burn short':>11}{'burn long':>11}"
                 f"{'thresh':>8}  since")
            for a in alerts:
                mark = "*" if a.get("firing") else " "
                since = a.get("since")
                t = "-" if not since else \
                    datetime.datetime.fromtimestamp(since).strftime(
                        "%H:%M:%S")
                emit(f"  {mark}{a.get('slo', '?'):<24}"
                     f"{a.get('short_burn', 0):>11.2f}"
                     f"{a.get('long_burn', 0):>11.2f}"
                     f"{a.get('burn_threshold', 0):>8g}  {t}")
        decisions = eng.get("decisions", [])
        if decisions:
            emit(f"\n  {'time':<14}{'slo':<25}{'state':<10}"
                 f"{'burn s/l':<16}")
            for d in decisions:
                t = datetime.datetime.fromtimestamp(d["ts"]).strftime(
                    "%H:%M:%S.%f")[:-3]
                burns = (f"{d.get('short_burn', 0):.2f}/"
                         f"{d.get('long_burn', 0):.2f}")
                emit(f"  {t:<14}{d.get('slo', '?'):<25}"
                     f"{d.get('state', '?'):<10}{burns:<16}")


def render_kernels(doc, prefix="", out=None):
    """Kernel-plane panel from the ``zoo_kernel_*`` gauge family
    (parallel/plan.py record_kernel_gauges + ops/pallas
    record_kernel_bytes): per-label scope→kernel selections from the
    plan's fifth rule table, measured-vs-predicted custom-call bytes
    with their relative error, and the per-kernel pallas/fallback
    routing counters.  Skipped when the snapshot carries no zoo_kernel
    samples or ``--prefix`` filters them out."""
    if prefix and not "zoo_kernel".startswith(prefix):
        return
    samples = [s for s in doc.get("samples", [])
               if s["name"].startswith("zoo_kernel_")]
    if not samples:
        return
    emit = print if out is None else (lambda s: out.append(s))
    selections = [s for s in samples
                  if s["name"] == "zoo_kernel_selections"]
    if selections:
        emit(f"\nkernels: {'label':<22}{'scope':<22}kernel")
        for s in sorted(selections,
                        key=lambda s: (s["labels"].get("label", ""),
                                       s["labels"].get("scope", ""))):
            lab = s["labels"]
            emit(f"         {lab.get('label', '?'):<22}"
                 f"{lab.get('scope', '?'):<22}{lab.get('kernel', '?')}")
    by_label = {}
    for s in samples:
        if s["name"] in ("zoo_kernel_measured_bytes",
                         "zoo_kernel_predicted_bytes",
                         "zoo_kernel_bytes_rel_error"):
            by_label.setdefault(
                s["labels"].get("label", "?"), {})[s["name"]] = s["value"]
    if by_label:
        emit(f"\n  {'label':<28}{'measured':>12}{'predicted':>12}"
             f"{'rel_err':>9}")
        for label in sorted(by_label):
            row = by_label[label]
            pred = row.get("zoo_kernel_predicted_bytes")
            err = row.get("zoo_kernel_bytes_rel_error")
            emit(f"  {label:<28}"
                 f"{row.get('zoo_kernel_measured_bytes', 0):>12.0f}"
                 f"{('-' if pred is None else f'{pred:.0f}'):>12}"
                 f"{('-' if err is None else f'{err:.4f}'):>9}")
    invocations = [s for s in samples
                   if s["name"] == "zoo_kernel_invocations"]
    if invocations:
        emit(f"\n  {'kernel':<24}{'backend':<12}count")
        for s in sorted(invocations,
                        key=lambda s: (s["labels"].get("kernel", ""),
                                       s["labels"].get("backend", ""))):
            lab = s["labels"]
            emit(f"  {lab.get('kernel', '?'):<24}"
                 f"{lab.get('backend', '?'):<12}{s['value']:.0f}")


def render(docs, a):
    """One full render pass over a snapshot list — the body shared by
    the one-shot path and the ``--watch`` loop."""
    first, last = docs[0], docs[-1]
    first_vals = {_key(s): s for s in first.get("samples", [])}
    dt = max(last.get("ts", 0) - first.get("ts", 0), 0.0)

    hist_rows, val_rows = [], []
    for s in last.get("samples", []):
        key = _key(s)
        if a.prefix and not s["name"].startswith(a.prefix):
            continue
        if s["kind"] == "histogram":
            unit_vals = [_scale(key, s[k])[0]
                         for k in ("mean", "p50", "p95", "p99")]
            unit = _scale(key, 0.0)[1]
            hist_rows.append((key, int(s["count"]), unit) +
                             tuple(unit_vals))
        else:
            v = s.get("value", 0.0)
            delta = ""
            rate = ""
            prev = first_vals.get(key)
            if prev is not None and len(docs) > 1 \
                    and s["kind"] == "counter":
                d = v - prev.get("value", 0.0)
                delta = f"{d:+.6g}"
                if dt > 0:
                    rate = f"{d / dt:.6g}/s"
            val_rows.append((key, s["kind"], f"{v:.6g}", delta, rate))

    if a.prometheus:
        for row in val_rows:
            print(f"{row[0]} {row[2]}")
        for row in hist_rows:
            print(f"{row[0]}_count {row[1]}")
        return

    src = a.url if a.url else a.path
    print(f"# {src}: {len(docs)} snapshot(s), window {dt:.1f}s")
    render_autotune(last, prefix=a.prefix)
    render_fleet(last, prefix=a.prefix)
    render_router(last, prefix=a.prefix)
    render_admission(last, prefix=a.prefix)
    render_oracle(last, prefix=a.prefix)
    render_elastic(last, prefix=a.prefix)
    render_scrape(last, prefix=a.prefix)
    render_slo(last, prefix=a.prefix)
    render_kernels(last, prefix=a.prefix)
    if hist_rows:
        print(f"\n{'histogram':<52}{'count':>9}{'mean':>11}"
              f"{'p50':>11}{'p95':>11}{'p99':>11}")
        for key, count, unit, mean, p50, p95, p99 in hist_rows:
            u = f" {unit}" if unit else ""
            print(f"{key:<52}{count:>9}"
                  f"{mean:>10.3f}{u}{p50:>10.3f}{u}"
                  f"{p95:>10.3f}{u}{p99:>10.3f}{u}")
    if val_rows:
        print(f"\n{'metric':<52}{'kind':>9}{'value':>14}"
              f"{'delta':>12}{'rate':>12}")
        for key, kind, v, delta, rate in val_rows:
            print(f"{key:<52}{kind:>9}{v:>14}{delta:>12}{rate:>12}")


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("path", nargs="?", help="JSONL metrics file")
    p.add_argument("--url", default=None,
                   help="scrape a live /varz endpoint instead of "
                        "reading a file (http://host:port[/varz] or "
                        "host:port)")
    p.add_argument("--prefix", default="",
                   help="only metrics whose name starts with this")
    p.add_argument("--prometheus", action="store_true",
                   help="ignored for histograms' full buckets (JSONL "
                        "carries summaries); prints name=value lines "
                        "instead of the table")
    p.add_argument("--watch", type=float, default=None, metavar="SECONDS",
                   help="re-fetch and re-render every SECONDS (live "
                        "panel; Ctrl-C to stop).  In --url mode each "
                        "refresh keeps the previous scrape as the "
                        "baseline, so counter deltas/rates become live")
    a = p.parse_args()

    if bool(a.path) == bool(a.url):
        p.error("exactly one of PATH or --url is required")
    if a.watch is not None and a.watch <= 0:
        p.error("--watch needs a positive interval")
    if a.watch is not None and a.prometheus:
        p.error("--watch and --prometheus do not combine")

    docs = fetch(a.url) if a.url else load(a.path)
    if a.watch is None:
        render(docs, a)
        return

    import time
    prev = docs[-1]
    try:
        while True:
            # clear + home, like watch(1), so the panel repaints in
            # place; harmless when stdout is not a terminal
            if sys.stdout.isatty():
                print("\x1b[2J\x1b[H", end="")
            render(docs, a)
            sys.stdout.flush()
            time.sleep(a.watch)
            try:
                fresh = fetch(a.url) if a.url else load(a.path)
            except SystemExit as e:
                # a restarting endpoint shouldn't kill the panel
                print(f"(refresh failed: {e})", file=sys.stderr)
                continue
            # live baseline: previous scrape first, newest last
            docs = [prev, fresh[-1]] if a.url else fresh
            prev = fresh[-1]
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
