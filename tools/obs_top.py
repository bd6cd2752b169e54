"""obs_top — a live text dashboard over the observability layer.

Two data sources, one renderer:

  * ``--varz URL``  — scrape a running exporter's /varz (the trainer's
    ``obs.export_port`` or serve.py's ``--obs-port``) on an interval;
  * ``--jsonl PATH`` — tail a metrics JSONL file (a live run's
    ``--metrics-file``, or a committed demo artifact) and render its
    newest periodic record;
  * ``--fleet URL`` — scrape a FleetAggregator's rollup /varz
    (obs/fleet.py) and render the whole fleet: per-shard / per-replica /
    per-host rows (alive, p95s, occupancy), merged histograms, SLO rule
    states, and recent cross-tier trace timelines.
  * ``--timeline DIR`` — read a run's flight-data recorder
    (obs/timeline.py, the per-run on-disk snapshot ring) and render its
    gauge series as sparklines, windowed counter rates, per-rule SLO
    burn history, and the newest bucket exemplars — "what happened at
    minute 43", offline, after the run is gone.

Shows the fleet in one screen: learner throughput, per-worker actor
stats (env-steps/s, ε slice, ring backlog, heartbeat age — the shm
stats-block sweep), transport rates, and the true age-of-experience
histogram at sample time (obs/lineage).  ``--once`` prints a single
frame and exits; ``--snapshot-out FILE`` additionally writes the raw
snapshot + rendered frame as JSON (how ``demos/obs_top.json`` is made).

Stdlib only — this must run on any host that can reach the port.

    python tools/obs_top.py --varz http://127.0.0.1:8080 --interval 2
    python tools/obs_top.py --jsonl demos/longrun_metrics.jsonl --once
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request


def snapshot_from_varz(url: str, timeout: float = 5.0) -> dict:
    """One /varz scrape, normalized (the exporter already emits the
    sectioned layout the renderer wants)."""
    base = url.rstrip("/")
    if not base.endswith("/varz"):
        base += "/varz"
    with urllib.request.urlopen(base, timeout=timeout) as r:
        return json.load(r)


def snapshot_from_jsonl(path: str) -> dict:
    """The newest periodic record of a metrics JSONL stream, lifted into
    the /varz sectioned shape (top-level learner scalars → ``learner``;
    ``workers`` / ``lineage`` / ``xp_transport`` ride through)."""
    last = None
    launch = {}  # the `launch` event: the partition of the run's launch
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue  # torn tail of a live file
            if "step" in rec and "event" not in rec:
                last = rec
            elif rec.get("event") == "launch":
                launch = rec
    if last is None:
        raise ValueError(f"no periodic records in {path}")
    learner_keys = (
        "step", "steps_per_sec", "actor_fps", "actor_steps",
        "param_version", "actor_restarts", "actor_heartbeat_age",
        "replay_size",
    )
    out = {"learner": {k: last[k] for k in learner_keys if k in last}}
    for section in ("workers", "lineage", "xp_transport", "ckpt",
                    "stage_us", "net", "serving_net", "serving_router",
                    "replay_svc"):
        if section in last:
            out[section] = last[section]
    # the periodic records carry the compiles since the launch ended
    if launch or "launch" in last:
        out["launch"] = {**launch, **last.get("launch", {})}
    out["t"] = last.get("t")
    return out


def snapshot_from_timeline(dir_path: str) -> dict:
    """Whole-timeline load (obs/timeline.py is import-light; the lazy
    import keeps obs_top's other modes runnable from a bare checkout of
    just this file)."""
    import os
    import sys as _sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in _sys.path:
        _sys.path.insert(0, repo)
    from ape_x_dqn_tpu.obs.timeline import read_timeline

    doc = read_timeline(dir_path)
    if not doc["records"]:
        raise ValueError(f"no timeline records under {dir_path}")
    return doc


_SPARK = "▁▂▃▄▅▆▇█"

# Gauge series render order + formats for the timeline view.
_TL_GAUGES = (
    ("serving_qps", "serving qps", "{:.1f}"),
    ("serving_p99_ms", "serving p99 ms", "{:.2f}"),
    ("replay_add_qps", "replay add qps", "{:.1f}"),
    ("age_p95_s", "age p95 s", "{:.2f}"),
    ("replay_occupancy", "replay occupancy", "{:.3f}"),
    ("ring_occupancy_max", "ring occupancy", "{:.3f}"),
    ("alive", "endpoints alive", "{:.0f}"),
)


def _sparkline(values, width: int = 48) -> str:
    """Downsample a series to ``width`` columns (mean per column) and
    render each as one of 8 block heights, scaled min..max."""
    vals = [float(v) for v in values]
    if not vals:
        return ""
    if len(vals) > width:
        step = len(vals) / width
        vals = [
            sum(vals[int(i * step):max(int(i * step) + 1,
                                       int((i + 1) * step))])
            / max(1, int((i + 1) * step) - int(i * step))
            for i in range(width)
        ]
    lo, hi = min(vals), max(vals)
    span = hi - lo
    if span <= 0:
        return _SPARK[0] * len(vals)
    return "".join(
        _SPARK[min(len(_SPARK) - 1,
                   int((v - lo) / span * len(_SPARK)))]
        for v in vals
    )


def render_timeline(doc: dict) -> str:
    """One frame over a loaded timeline: per-gauge sparklines with
    min/max/last, windowed counter totals, SLO burn history per rule,
    and the newest exemplar trace ids."""
    recs = doc.get("records") or []
    if not recs:
        return "(empty timeline)"
    t0 = float(recs[0].get("t", 0.0))
    t1 = float(recs[-1].get("t", 0.0))
    span = max(t1 - t0, 0.0)
    lines = [
        "== apex-tpu timeline ==  "
        f"{len(recs)} records over {span:.0f}s  "
        f"{doc.get('segments', 0)} segments  "
        f"torn {doc.get('torn', 0)}"
    ]
    for key, label, fmt in _TL_GAUGES:
        series = [r["gauges"][key] for r in recs
                  if (r.get("gauges") or {}).get(key) is not None]
        if not series:
            continue
        lines.append(
            f" {label:<18} {_sparkline(series)}  "
            f"min {_num(min(series), fmt)} "
            f"max {_num(max(series), fmt)} "
            f"last {_num(series[-1], fmt)}"
        )
    totals: dict = {}
    for r in recs:
        for k, v in (r.get("counters") or {}).items():
            totals[k] = totals.get(k, 0) + int(v)
    if totals:
        lines.append(
            "-- counters (whole span): "
            + "  ".join(
                f"{k} {totals[k]}"
                + (f" ({totals[k] / span:.1f}/s)" if span > 0 else "")
                for k in sorted(totals)
            )
        )
    rules: dict = {}
    for r in recs:
        for name, ent in (r.get("slo") or {}).items():
            rules.setdefault(name, []).append(ent)
    if rules:
        lines.append(f"-- slo burn history ({len(rules)} rules) " + "-" * 24)
        for name in sorted(rules):
            ents = rules[name]
            xs = [e.get("x") for e in ents if e.get("x") is not None]
            burn = (sum(xs) / len(xs)) if xs else 0.0
            marks = "".join(
                "!" if e.get("s") == "breach" else
                ("x" if e.get("x") else ".")
                for e in ents[-48:]
            )
            lines.append(
                f" {name:<24} {ents[-1].get('s', '?'):<7}"
                f"burn {burn:.2f}  [{marks}]"
            )
    newest_ex = next(
        (r["exemplars"] for r in reversed(recs) if r.get("exemplars")),
        None,
    )
    if newest_ex:
        lines.append("-- exemplars (newest trace id per latency bucket) --")
        for key in sorted(newest_ex):
            pairs = list(newest_ex[key].items())[-4:]
            lines.append(
                f" {key:<14} "
                + "  ".join(f"<= {edge}s: {tid}" for edge, tid in pairs)
            )
    return "\n".join(lines)


def _bar(count: int, peak: int, width: int = 30) -> str:
    n = 0 if peak <= 0 else max(1, round(count / peak * width))
    return "#" * min(n, width)


def _fmt_age(edge: str) -> str:
    if edge == "+Inf":
        return "   +Inf"
    return f"{float(edge):7.3g}"


def _num(v, fmt: str = "{:.1f}", dash: str = "-") -> str:
    if v is None:
        return dash
    try:
        return fmt.format(float(v))
    except (TypeError, ValueError):
        return str(v)


def render_fleet(snap: dict) -> str:
    """One fleet frame from a FleetAggregator /varz snapshot: endpoint
    rows by kind, the merged rollup line, SLO rule states, and the
    newest cross-tier trace timelines."""
    fleet = snap.get("fleet") or {}
    slo = snap.get("slo") or {}
    eps = fleet.get("endpoints") or {}
    breaching = slo.get("breaching") or []
    lines = [
        "== apex-tpu fleet ==  "
        f"{fleet.get('alive', 0)}/{fleet.get('expected', 0)} endpoints up  "
        f"scrapes {fleet.get('scrapes', 0)} "
        f"({fleet.get('scrape_failures', 0)} failed)  "
        f"SLO {'BREACH[' + ','.join(breaching) + ']' if breaching else 'ok'}"
    ]
    age = fleet.get("age_of_experience") or {}
    srv = fleet.get("serving") or {}
    inf = fleet.get("inference") or {}
    rep = fleet.get("replay") or {}
    occ = fleet.get("ring_occupancy_max")
    lines.append(
        f"-- merged: age p95 {_num(age.get('p95_s'), '{:.2f}')}s "
        f"(n={age.get('count', 0)})  "
        f"serving p99 {_num(srv.get('p99_ms'))} ms "
        f"qps {_num(srv.get('qps'))}  "
        f"inference rtt p99 {_num(inf.get('rtt_p99_ms_max'))} ms  "
        f"replay op p95 {_num(rep.get('op_p95_ms'), '{:.2f}')} ms "
        f"add {_num(rep.get('add_qps'))}/s  "
        f"ring occ {_num(occ, '{:.3f}')}"
    )
    for kind, title in (("trainer", "hosts/trainers"), ("shard", "shards"),
                        ("replica", "replicas"), ("host", "hosts")):
        rows = {n: e for n, e in eps.items() if e.get("kind") == kind}
        if not rows:
            continue
        lines.append(f"-- {title} ({len(rows)}) " + "-" * 40)
        for name in sorted(rows):
            e = rows[name]
            d = e.get("detail") or {}
            if kind == "shard":
                extra = (f"size {d.get('size', '-'):>8}  "
                         f"req {d.get('requests', '-'):>7}  "
                         f"p95 {_num(d.get('p95_ms'), '{:.2f}'):>8} ms  "
                         f"inc {d.get('incarnation', '-')}")
            elif kind == "replica":
                extra = (f"req {d.get('requests', '-'):>7}  "
                         f"p95 {_num(d.get('p95_ms'), '{:.2f}'):>8} ms  "
                         f"shed {d.get('shed', '-')}  "
                         f"v{d.get('param_version', '?')}")
            else:
                extra = (f"step {d.get('step', '-'):>8}  "
                         f"{_num(d.get('steps_per_sec')):>8} steps/s  "
                         f"workers {d.get('workers', '-')}  "
                         f"age p95 {_num(d.get('age_p95_ms'))} ms")
            lines.append(
                f" {name:<16} {'up  ' if e.get('alive') else 'DOWN':<5}"
                f"fails {e.get('scrape_failures', 0):>4}  " + extra
            )
    mem = fleet.get("membership")
    if mem:
        draining = mem.get("draining") or []
        by_kind = mem.get("by_kind") or {}
        kinds = " ".join(f"{k}:{by_kind[k]}" for k in sorted(by_kind))
        lines.append(
            f"-- membership v{mem.get('version', 0)}  "
            f"{mem.get('members', 0)} members ({kinds})  "
            f"adopted {mem.get('adopted_endpoints', 0)} eps "
            f"({mem.get('adopts', 0)} adopts)  "
            + (f"DRAINING[{','.join(draining)}]" if draining else "steady")
        )
    rules = (slo.get("rules") or {})
    if rules:
        lines.append(f"-- slo rules ({len(rules)}) " + "-" * 40)
        for name in sorted(rules):
            r = rules[name]
            lines.append(
                f" {name:<24} {r.get('state', '?'):<7}"
                f"value {_num(r.get('value'), '{:.3f}'):>10}  "
                f"{'<=' if r.get('kind') == 'upper' else '>='} "
                f"{_num(r.get('bound'), '{:.3f}')}  "
                f"burn {_num(r.get('burn'), '{:.2f}')} "
                f"({r.get('samples', 0)} samples)  "
                f"b/c {r.get('breaches', 0)}/{r.get('clears', 0)}"
            )
    ap = fleet.get("autopilot") or snap.get("autopilot")
    if ap:
        fleets = ap.get("fleets") or {}
        lines.append(
            f"-- autopilot {'DRY-RUN ' if ap.get('dry_run') else ''}"
            f"({ap.get('actions', 0)} actions, "
            f"{ap.get('decisions', 0)} decisions) " + "-" * 24
        )
        for name in sorted(fleets):
            f = fleets[name]
            breaching = f.get("breaching") or []
            lines.append(
                f" {name:<10} size {f.get('size', '?')}"
                f" [{f.get('min', '?')}..{f.get('max', '?')}]"
                f"{' BOOTING' if f.get('busy') else '':<9}"
                f"last {f.get('last_action') or '-'}"
                f"({f.get('last_rule') or '-'})  "
                f"cd up/down {_num(f.get('cooldown_up_s'), '{:.0f}')}/"
                f"{_num(f.get('cooldown_down_s'), '{:.0f}')}s  "
                f"{'BREACH[' + ','.join(breaching) + ']' if breaching else 'green'}"
            )
    traces = fleet.get("traces") or []
    if traces:
        lines.append(f"-- traces ({len(traces)} recent timelines) " + "-" * 24)
        for t in traces[:4]:
            hops = " -> ".join(
                f"{s.get('hop')}@{s.get('pid')}"
                f"({_num(s.get('dur_ms'), '{:.1f}')}ms)"
                for s in t.get("spans", [])
            )
            lines.append(f" {t.get('trace_id')}: {hops}")
    return "\n".join(lines)


def render(snap: dict) -> str:
    """One dashboard frame (plain text) from a /varz-shaped snapshot."""
    lines = []
    ln = snap.get("learner", {})
    lines.append(
        "== apex-tpu obs_top ==  "
        f"step {ln.get('step', '?')}  "
        f"learner {ln.get('steps_per_sec', 0):>8} steps/s  "
        f"actors {ln.get('actor_fps', 0):>8} fps  "
        f"replay {ln.get('replay_size', '?')}  "
        f"v{ln.get('param_version', '?')}"
    )
    launch = snap.get("launch")
    if launch:
        lines.append(
            f"-- launch  {launch.get('seconds', 0):.1f} s to step "
            f"{launch.get('step', '?')}: "
            + "  ".join(  # the nine parts: the record's keys in `_s`
                f"{part[:-2]} {value:.1f}" for part, value in launch.items()
                if part.endswith("_s"))
            + f"  cache {launch.get('cache_hits', '?')} hit/"
            f"{launch.get('cache_misses', '?')} miss  "
            f"compiles_after_launch {launch.get('compiles_after_launch', 0)}"
        )
        for rc in (launch.get("recompiles") or [])[-3:]:
            lines.append(
                f"   recompile  {rc.get('program')}  step {rc.get('step')}  "
                f"{rc.get('seconds', 0):.3f} s  cache {rc.get('cache')}  "
                f"({rc.get('thread')})"
            )
    workers = snap.get("workers") or {}
    if workers:
        lines.append(
            f"-- workers ({len(workers)}) "
            "----------------------------------------------------------"
        )
        lines.append(
            " wid   alive  steps/s   env_steps  chunks      eps"
            "[min..max]    ring_kB  hb_age"
        )
        for wid in sorted(workers, key=lambda w: int(w)):
            w = workers[wid]
            lines.append(
                f"{wid:>4}   {'yes' if w.get('alive') else ' NO':<5}"
                f"{w.get('env_steps_s', 0):>9.1f}"
                f"{int(w.get('env_steps', 0)):>12}"
                f"{int(w.get('chunks', 0)):>8}"
                f"   {w.get('eps_mean', 0):.3f}"
                f"[{w.get('eps_min', 0):.3f}..{w.get('eps_max', 0):.3f}]"
                f"{w.get('ring_backlog_bytes', 0) / 1e3:>9.1f}"
                f"{w.get('heartbeat_age_s', 0):>8.2f}"
            )
    xp = snap.get("xp_transport")
    if xp:
        lines.append(
            f"-- transport: {xp.get('ingest_mb_s', 0)} MB/s  "
            f"{xp.get('transitions_s', 0)} transitions/s  "
            f"chunks {xp.get('chunks', 0)}  "
            f"salvaged {xp.get('salvaged_records', 0)}  "
            f"torn {xp.get('torn_records', 0)}  "
            f"full_waits {xp.get('ring_full_waits', 0)}"
        )
    lineage = snap.get("lineage") or {}
    age = lineage.get("age_at_sample") or {}
    buckets = age.get("buckets_s") or age.get("buckets") or {}
    if buckets:
        lines.append(
            f"-- age of experience at sample (s): "
            f"n={age.get('count', 0)} p50={age.get('p50_ms', 0) / 1e3:.2f} "
            f"p99={age.get('p99_ms', 0) / 1e3:.2f} "
            f"max={age.get('max_ms', 0) / 1e3:.2f}"
        )
        peak = max(buckets.values())
        for edge, count in buckets.items():
            lines.append(
                f"  <= {_fmt_age(edge)}s {count:>8}  {_bar(count, peak)}"
            )
        lines.append(
            f"-- lineage: {lineage.get('traces_completed', 0)} spans done, "
            f"{lineage.get('traces_open', 0)} open, "
            f"{lineage.get('traces_abandoned', 0)} abandoned"
        )
    ckpt = snap.get("ckpt")
    if ckpt:
        lines.append(
            f"-- ckpt: {ckpt.get('saves', 0)} saves "
            f"({ckpt.get('bases', 0)} bases) "
            f"last_stall {ckpt.get('last_stall_ms', 0)} ms  "
            f"skips {ckpt.get('inflight_skips', 0)}"
        )
    xnet = snap.get("net")
    if xnet:
        ratio = xnet.get("wire_over_logical")
        lines.append(
            f"-- xp net  conns {xnet.get('connections', 0)}"
            f"/{xnet.get('expected', 0)}  "
            f"{(xnet.get('bytes_in_per_s') or 0) / 1e6:8.1f} MB/s wire  "
            f"ratio {ratio if ratio is not None else '-'}  "
            f"rec/frame {xnet.get('records_per_frame', '-')}  "
            f"codec {xnet.get('codec', 'off')} "
            f"({xnet.get('codec_ms', 0)} ms)  "
            f"torn {xnet.get('torn_frames', 0)}"
        )
    rsvc = snap.get("replay_svc")
    if rsvc:
        down = rsvc.get("down") or []
        lines.append(
            f"-- replay svc  {rsvc.get('shards', 0) - len(down)}"
            f"/{rsvc.get('shards', 0)} shards up"
            + (f" (down {down}, {rsvc.get('degraded_age_s', 0)}s)"
               if down else "")
            + f"  size {rsvc.get('size', 0)}  "
            f"s/a/u {rsvc.get('samples', 0)}/{rsvc.get('adds', 0)}"
            f"/{rsvc.get('updates', 0)}  "
            f"wb pend {rsvc.get('writeback_pending', 0)} "
            f"flushed {rsvc.get('writeback_flushed', 0)}  "
            f"torn {rsvc.get('rpc_torn', 0)}"
        )
    inf = snap.get("inference")
    if inf:
        rtt = inf.get("rtt") or {}
        lag = inf.get("version_lag")
        occ = inf.get("batch_occupancy_mean")
        lines.append(
            f"-- inference  {inf.get('mode', '-')}  "
            f"{inf.get('replies', 0)} replies "
            f"({inf.get('workers_reporting', 0)} workers)  "
            f"rtt p50/p99 {rtt.get('p50_ms', '-')}/"
            f"{rtt.get('p99_ms', '-')} ms  "
            f"occ {occ if occ is not None else '-'}  "
            f"lag {lag if lag is not None else '-'}  "
            f"stall {inf.get('stall_ms', 0)} ms  "
            f"torn {inf.get('torn_replies', 0)}  "
            f"fb {inf.get('fallback_steps', 0)}"
        )
    snet = snap.get("serving_net") or (snap.get("serving") or {}).get("net")
    if snet:
        lat = snet.get("latency") or {}
        lines.append(
            f"-- serving net :{snet.get('port', '?')}  "
            f"conns {snet.get('connections', 0)}  "
            f"req {snet.get('requests', 0)}  "
            f"shed {snet.get('shed', 0)}  "
            f"torn {snet.get('torn_frames', 0)}  "
            f"p99 {lat.get('p99_ms', 0)} ms  "
            f"v{snet.get('param_version', '?')}"
        )
    rt = snap.get("serving_router")
    if rt:
        lines.append(
            f"-- router :{rt.get('port', '?')}  "
            f"{rt.get('healthy', 0)}/{rt.get('replicas', 0)} healthy  "
            f"active {rt.get('active', 0)}  "
            f"routed {rt.get('routed_total', 0)}  "
            f"fails {rt.get('route_fails', 0)}  "
            f"broken {rt.get('splices_broken', 0)}"
        )
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="obs_top")
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument("--varz", metavar="URL",
                     help="exporter base URL or full /varz URL")
    src.add_argument("--jsonl", metavar="PATH",
                     help="metrics JSONL file to tail")
    src.add_argument("--fleet", metavar="URL",
                     help="FleetAggregator rollup URL (obs/fleet.py) — "
                     "renders per-shard/replica/host rows + SLO states")
    src.add_argument("--timeline", metavar="DIR",
                     help="flight-data recorder directory "
                     "(obs/timeline.py) — renders gauge sparklines, "
                     "SLO burn history and exemplars from disk")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--once", action="store_true",
                    help="print one frame and exit")
    ap.add_argument("--plain", action="store_true",
                    help="no ANSI clear between frames")
    ap.add_argument("--snapshot-out", default=None, metavar="FILE",
                    help="also write {snapshot, rendered} JSON here")
    args = ap.parse_args(argv)

    def grab() -> dict:
        if args.varz:
            return snapshot_from_varz(args.varz)
        if args.fleet:
            return snapshot_from_varz(args.fleet)
        if args.timeline:
            return snapshot_from_timeline(args.timeline)
        return snapshot_from_jsonl(args.jsonl)

    while True:
        try:
            snap = grab()
            if args.fleet:
                frame = render_fleet(snap)
            elif args.timeline:
                frame = render_timeline(snap)
            else:
                frame = render(snap)
        except Exception as e:  # noqa: BLE001 — a scrape gap, keep going
            snap, frame = {}, f"(no data: {type(e).__name__}: {e})"
        if not args.plain and not args.once:
            sys.stdout.write("\x1b[2J\x1b[H")
        print(frame, flush=True)
        if args.snapshot_out and snap:
            with open(args.snapshot_out, "w") as f:
                json.dump(
                    {"snapshot": snap, "rendered": frame.splitlines()},
                    f, indent=1,
                )
        if args.once:
            return 0
        time.sleep(args.interval)


if __name__ == "__main__":
    raise SystemExit(main())
