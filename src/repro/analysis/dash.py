"""Live fleet dashboard: stdlib HTTP server over a structured event journal.

:class:`AnalysisNotificationProvider` is a ``NotificationProvider`` that tees
every engine event — ``task_started``/``task_finished``/``task_failed`` from
``Memento.stream`` / the Runner, plus the distributed driver's periodic
``queue_progress`` snapshots — into

* an append-only JSONL **journal** (optional; on a shared filesystem any
  host can tail it), and
* live in-memory **aggregates**: totals, ETA, per-host throughput and task
  rates, latest serve metrics (accept rate, inter-token latency), queue
  depth, and a failure list carrying the *real* tracebacks the distributed
  runtime propagates.

:class:`Dashboard` serves those aggregates with nothing but ``http.server``:

* ``GET /``               one-page live view (polling JS, no dependencies)
* ``GET /api/state``      the aggregate snapshot as JSON
* ``GET /api/events``     the journal tail (``?since=<cursor>`` to page)
* ``GET /api/stream``     Server-Sent Events: state snapshots pushed ~1/s

Pair with ``python -m repro.analysis dash --journal <path>`` to watch a run
owned by another process (or a whole fleet writing to one shared journal).
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Mapping

from repro.core.notifications import Event, NotificationProvider

from .metrics import _as_float

# Serve-sweep metrics worth surfacing verbatim on the fleet view when a
# task's result carries them (see experiments/serve.py SERVE_METRIC_SPECS).
_SERVE_KEYS = (
    "tokens_per_s", "itl_p50_s", "itl_p95_s", "accept_rate",
    "tokens_per_model_step", "ttft_p50_s",
)


class AnalysisNotificationProvider(NotificationProvider):
    """Tee engine events into a JSONL journal + live fleet aggregates.

    Use either as the engine's ``notification_provider`` (events arrive via
    :meth:`notify`) or wrapped around a stream (``for r in prov.track(
    eng.stream_distributed(...))``) — or both; task results surfaced through
    ``track`` are de-duplicated against ones already seen via events.
    """

    def __init__(
        self,
        journal_path: str | Path | None = None,
        total: int | None = None,
        max_events: int = 4096,
    ):
        self.journal_path = Path(journal_path) if journal_path else None
        if self.journal_path is not None:
            self.journal_path.parent.mkdir(parents=True, exist_ok=True)
        self.total = total
        self._events: deque[dict[str, Any]] = deque(maxlen=max_events)
        self._seq = 0  # cursor of the *next* event (monotonic, survives eviction)
        self._t0: float | None = None
        self._done_keys: set[str] = set()
        self._failed = 0
        self._cached = 0
        self._hosts: dict[str, dict[str, Any]] = {}
        self._failures: deque[dict[str, Any]] = deque(maxlen=256)
        self._queue: dict[str, Any] | None = None
        self._serve: dict[str, float] = {}
        self._lock = threading.Lock()

    # -- ingestion ----------------------------------------------------------
    def notify(self, event: Event) -> None:
        self.ingest(event.to_record())

    def ingest(self, rec: Mapping[str, Any]) -> None:
        """One structured event record (from :meth:`notify` or a replayed
        journal line). Journal writes happen only for live events, not
        replays — replay marks records with ``_replayed``."""
        rec = dict(rec)
        replayed = rec.pop("_replayed", False)
        with self._lock:
            self._ingest_locked(rec)
            self._seq += 1
            self._events.append(rec)
        if self.journal_path is not None and not replayed:
            line = json.dumps(rec, default=str)
            with self._lock:
                with open(self.journal_path, "a") as f:
                    f.write(line + "\n")

    def _ingest_locked(self, rec: Mapping[str, Any]) -> None:
        kind = rec.get("kind")
        t = _as_float(rec.get("t")) or time.time()
        if kind == "run_started":
            if self._t0 is None:
                self._t0 = t
            total = rec.get("total")
            if self.total is None and isinstance(total, int):
                self.total = total
            return
        if kind == "queue_progress":
            self._queue = {k: v for k, v in rec.items()
                           if k not in ("kind", "message", "t")}
            return
        if kind not in ("task_finished", "task_failed"):
            return
        key = str(rec.get("key", ""))
        if key and key in self._done_keys:
            return  # track() + notify() double-report the same task
        self._done_keys.add(key or f"@{self._seq}")
        if self._t0 is None:
            self._t0 = t
        host = str(rec.get("host") or "?")
        h = self._hosts.setdefault(
            host,
            {"done": 0, "failed": 0, "cached": 0, "wall_s": 0.0, "tokens": 0.0,
             "first_t": t, "last_t": t, "metrics": {}},
        )
        h["done"] += 1
        h["last_t"] = max(h["last_t"], t)
        h["wall_s"] += _as_float(rec.get("wall_s")) or 0.0
        if rec.get("cached"):
            self._cached += 1
            h["cached"] += 1
        metrics = rec.get("metrics")
        if isinstance(metrics, Mapping):
            h["tokens"] += metrics.get("generated_tokens", 0.0) or 0.0
            latest = {k: metrics[k] for k in _SERVE_KEYS
                      if metrics.get(k) is not None}
            if latest:
                h["metrics"] = latest
                self._serve.update(latest)
        if kind == "task_failed":
            self._failed += 1
            h["failed"] += 1
            self._failures.append(
                {
                    "key": key,
                    "params": rec.get("params") or {},
                    "host": host,
                    "error": rec.get("error"),
                    "traceback": rec.get("traceback"),
                    "attempts": rec.get("attempts"),
                    "t": t,
                }
            )

    def track(self, results: Any) -> Any:
        """Wrap a result stream: every ``TaskResult`` passes through
        unchanged while being folded into the aggregates (cache hits
        included — they bypass execution and therefore events)."""
        for result in results:
            try:
                self.task_finished(result)
            except Exception:
                pass  # providers must never take the run down
            yield result

    def replay_journal(self, path: str | Path | None = None, offset: int = 0) -> int:
        """Feed journal lines (JSONL event records) starting at byte
        ``offset``; returns the new offset — poll it to tail a live run."""
        p = Path(path or self.journal_path or "")
        try:
            with open(p) as f:
                f.seek(offset)
                for line in f:
                    if not line.endswith("\n"):
                        break  # half-written tail; pick it up next poll
                    offset += len(line.encode())
                    try:
                        rec = json.loads(line)
                    except json.JSONDecodeError:
                        continue
                    rec["_replayed"] = True
                    self.ingest(rec)
        except OSError:
            pass
        return offset

    # -- queries ------------------------------------------------------------
    def eta_s(self) -> float | None:
        with self._lock:
            return self._eta_locked()

    def _eta_locked(self) -> float | None:
        done = len(self._done_keys)
        live = done - self._cached
        if self.total is None or self._t0 is None or live <= 0:
            return None
        remaining = max(self.total - done, 0)
        rate = live / max(time.time() - self._t0, 1e-9)
        return remaining / rate if rate > 0 else None

    def state(self) -> dict[str, Any]:
        """JSON-safe aggregate snapshot — the dashboard's /api/state body."""
        with self._lock:
            now = time.time()
            done = len(self._done_keys)
            hosts = {}
            for name, h in sorted(self._hosts.items()):
                elapsed = max(h["last_t"] - (self._t0 or h["first_t"]), 1e-9)
                hosts[name] = {
                    "done": h["done"],
                    "failed": h["failed"],
                    "cached": h["cached"],
                    "tasks_per_s": round(h["done"] / elapsed, 3),
                    "tokens_per_s": (
                        round(h["tokens"] / h["wall_s"], 2) if h["wall_s"] else None
                    ),
                    "metrics": dict(h["metrics"]),
                }
            queue = dict(self._queue) if self._queue else None
            return {
                "t": now,
                "total": self.total,
                "done": done,
                "failed": self._failed,
                "cached": self._cached,
                "running_s": (round(now - self._t0, 1) if self._t0 else None),
                "eta_s": (lambda e: None if e is None else round(e, 1))(
                    self._eta_locked()
                ),
                "hosts": hosts,
                "queue": queue,
                "serve": dict(self._serve),
                "failures": list(self._failures),
                "events_seen": self._seq,
            }

    def events_since(self, cursor: int = 0) -> tuple[int, list[dict[str, Any]]]:
        """Events with sequence >= cursor (bounded by the ring buffer);
        returns (next_cursor, records)."""
        with self._lock:
            first = self._seq - len(self._events)
            start = max(cursor, first)
            out = [self._events[i - first] for i in range(start, self._seq)]
            return self._seq, out


_INDEX_HTML = """<!doctype html>
<html lang="en"><head><meta charset="utf-8">
<title>memento fleet</title>
<style>
  :root { color-scheme: dark; }
  body { font: 14px/1.45 ui-monospace, SFMono-Regular, Menlo, monospace;
         background: #16161d; color: #e8e8ec; margin: 2rem; }
  h1 { font-size: 16px; font-weight: 600; color: #e8e8ec; }
  .muted { color: #9a9aa5; }
  .tiles { display: flex; gap: 12px; flex-wrap: wrap; margin: 1rem 0; }
  .tile { background: #1f1f29; border: 1px solid #2e2e3a; border-radius: 6px;
          padding: 10px 16px; min-width: 110px; }
  .tile b { display: block; font-size: 22px; font-weight: 600; }
  .tile span { font-size: 12px; color: #9a9aa5; }
  table { border-collapse: collapse; margin: .6rem 0 1.4rem; }
  th, td { text-align: right; padding: 4px 12px; border-bottom: 1px solid #2e2e3a; }
  th { color: #9a9aa5; font-weight: 500; }
  th:first-child, td:first-child { text-align: left; }
  .bad { color: #ff8a8a; }  /* status: failed — always beside a text label */
  .ok { color: #8fd9a8; }
  details { margin: .4rem 0; }
  pre { background: #1f1f29; border: 1px solid #2e2e3a; border-radius: 6px;
        padding: 8px 12px; overflow-x: auto; font-size: 12px; color: #c9c9d4; }
  #stale { display: none; color: #ffc94d; }
</style></head>
<body>
<h1>memento fleet <span class="muted" id="asof"></span>
  <span id="stale">(stale — no updates)</span></h1>
<div class="tiles" id="tiles"></div>
<h1>hosts</h1>
<table id="hosts"><thead><tr>
  <th>host</th><th>done</th><th>failed</th><th>cached</th>
  <th>tasks/s</th><th>tok/s</th><th>accept</th><th>itl p50</th>
</tr></thead><tbody></tbody></table>
<h1>queue</h1>
<table id="queue"><thead><tr>
  <th>host</th><th>claimed</th><th>done</th>
</tr></thead><tbody></tbody></table>
<h1>failures <span class="muted">(click to expand traceback)</span></h1>
<div id="failures" class="muted">none</div>
<script>
const fmt = (v, d=2) => v === null || v === undefined ? "-"
  : typeof v === "number" ? (Number.isInteger(v) ? v : v.toFixed(d)) : v;
const esc = s => String(s).replace(/[&<>]/g,
  c => ({"&":"&amp;","<":"&lt;",">":"&gt;"}[c]));
let lastSeen = 0, lastChange = Date.now();
function render(s) {
  document.getElementById("asof").textContent =
    "as of " + new Date(s.t * 1000).toLocaleTimeString();
  if (s.events_seen !== lastSeen) { lastSeen = s.events_seen; lastChange = Date.now(); }
  document.getElementById("stale").style.display =
    Date.now() - lastChange > 30000 ? "inline" : "none";
  const q = s.queue || {};
  const tiles = [
    ["done", `${s.done}${s.total ? "/" + s.total : ""}`],
    ["failed", s.failed, s.failed ? "bad" : ""],
    ["cached", s.cached],
    ["queue depth", q.total !== undefined ? q.total - q.done : "-"],
    ["ETA", s.eta_s !== null && s.eta_s !== undefined ? s.eta_s + "s" : "-"],
    ["running", s.running_s !== null ? s.running_s + "s" : "-"],
  ];
  document.getElementById("tiles").innerHTML = tiles.map(
    ([k, v, cls]) => `<div class="tile"><b class="${cls || ""}">${fmt(v)}</b>` +
      `<span>${k}</span></div>`).join("");
  document.querySelector("#hosts tbody").innerHTML =
    Object.entries(s.hosts).map(([h, v]) => `<tr><td>${esc(h)}</td>` +
      `<td>${v.done}</td><td class="${v.failed ? "bad" : ""}">${v.failed}</td>` +
      `<td>${v.cached}</td><td>${fmt(v.tasks_per_s)}</td>` +
      `<td>${fmt(v.tokens_per_s, 1)}</td>` +
      `<td>${fmt(v.metrics.accept_rate)}</td>` +
      `<td>${v.metrics.itl_p50_s !== undefined ?
             (v.metrics.itl_p50_s * 1000).toFixed(1) + "ms" : "-"}</td></tr>`
    ).join("") || `<tr><td class="muted">no completions yet</td></tr>`;
  const cb = q.claimed_by || {}, db = q.done_by || {};
  const qhosts = [...new Set([...Object.keys(cb), ...Object.keys(db)])].sort();
  document.querySelector("#queue tbody").innerHTML = qhosts.map(h =>
    `<tr><td>${esc(h)}</td><td>${cb[h] || 0}</td><td>${db[h] || 0}</td></tr>`
  ).join("") || `<tr><td class="muted">no queue (local run)</td></tr>`;
  document.getElementById("failures").innerHTML = s.failures.length
    ? s.failures.map(f => `<details><summary class="bad">` +
        `${esc(f.error || "failed")} — ${esc(JSON.stringify(f.params))} ` +
        `on ${esc(f.host)}</summary>` +
        `<pre>${esc(f.traceback || "(no traceback recorded)")}</pre>` +
        `</details>`).join("")
    : "none";
}
async function poll() {
  try { render(await (await fetch("/api/state")).json()); }
  catch (e) { document.getElementById("stale").style.display = "inline"; }
}
poll(); setInterval(poll, 1000);
</script>
</body></html>
"""


class Dashboard:
    """Serve an :class:`AnalysisNotificationProvider`'s live view over HTTP.

    >>> prov = AnalysisNotificationProvider(journal_path="run.jsonl")
    >>> dash = Dashboard(prov)           # port=0 -> ephemeral
    >>> url = dash.start()               # non-blocking; daemon thread
    >>> for r in prov.track(eng.stream_distributed(matrix, queue_dir=q)): ...
    >>> dash.stop()
    """

    def __init__(
        self,
        provider: AnalysisNotificationProvider,
        host: str = "127.0.0.1",
        port: int = 0,
    ):
        self.provider = provider
        self.host = host
        self.port = port
        self._server = None
        self._thread: threading.Thread | None = None

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def start(self) -> str:
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        provider = self.provider

        class Handler(BaseHTTPRequestHandler):
            daemon_threads = True

            def log_message(self, *args: Any) -> None:
                pass  # dashboards must never spam the run's stderr

            def _send(self, body: bytes, ctype: str, code: int = 200) -> None:
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.send_header("Cache-Control", "no-store")
                self.end_headers()
                self.wfile.write(body)

            def _json(self, obj: Any, code: int = 200) -> None:
                self._send(
                    json.dumps(obj, default=str).encode(),
                    "application/json", code,
                )

            def do_GET(self) -> None:  # noqa: N802 - http.server API
                from urllib.parse import parse_qs, urlparse

                u = urlparse(self.path)
                if u.path in ("/", "/index.html"):
                    self._send(_INDEX_HTML.encode(), "text/html; charset=utf-8")
                elif u.path == "/api/state":
                    self._json(provider.state())
                elif u.path == "/api/events":
                    q = parse_qs(u.query)
                    since = int(q.get("since", ["0"])[0] or 0)
                    cursor, events = provider.events_since(since)
                    self._json({"next": cursor, "events": events})
                elif u.path == "/api/stream":
                    self.send_response(200)
                    self.send_header("Content-Type", "text/event-stream")
                    self.send_header("Cache-Control", "no-store")
                    self.end_headers()
                    try:
                        while True:
                            body = json.dumps(provider.state(), default=str)
                            self.wfile.write(f"data: {body}\n\n".encode())
                            self.wfile.flush()
                            time.sleep(1.0)
                    except (BrokenPipeError, ConnectionResetError, OSError):
                        return  # client went away; the thread just ends
                else:
                    self._json({"error": f"no route {u.path}"}, 404)

        self._server = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever, name="memento-dash", daemon=True
        )
        self._thread.start()
        return self.url

    def stop(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None


def serve_journal(
    journal: str | Path,
    host: str = "127.0.0.1",
    port: int = 8321,
    follow: bool = True,
    poll_s: float = 0.5,
    total: int | None = None,
) -> tuple[Dashboard, AnalysisNotificationProvider]:
    """Dashboard over an existing journal file: replay what's there, then
    (with ``follow``) keep tailing it — how you watch a run owned by another
    process, or a whole fleet appending to one shared journal."""
    prov = AnalysisNotificationProvider(total=total)
    offset = prov.replay_journal(journal)
    dash = Dashboard(prov, host=host, port=port)
    dash.start()
    if follow:
        def tail() -> None:
            off = offset
            while True:
                time.sleep(poll_s)
                off = prov.replay_journal(journal, off)

        threading.Thread(target=tail, name="memento-dash-tail", daemon=True).start()
    return dash, prov
