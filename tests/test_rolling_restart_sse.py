"""Rolling restart with live SSE subscribers attached: drains complete,
the subscriber's ``Last-Event-ID`` resume reconnects through the
gateway onto a surviving replica, and ZERO published events are lost
across the whole fleet roll. Hermetic: light real-bus workers (the
actual ``serve/bus`` + ``serve/wsgi`` SSE path over the netbus broker,
no model), real supervisor + gateway, the real
``rolling_restart`` helper."""

import http.client
import json
import os
import socket
import sys
import threading
import time
import urllib.parse

from routest_tpu.core.config import FleetConfig
from routest_tpu.serve.fleet.gateway import Gateway
from routest_tpu.serve.fleet.rollout import rolling_restart
from routest_tpu.serve.fleet.supervisor import ReplicaSupervisor
from routest_tpu.serve.netbus import Broker, NetBus, start_broker

# A worker that serves the REAL SSE path (bus subscribe with
# Last-Event-ID resume → sse_stream) without the model stack: what a
# replica's /api/realtime_feed does, boots in ~1 s.
_SSE_WORKER = """
import os
from werkzeug.wrappers import Response
from routest_tpu.serve.bus import make_bus, sse_stream
from routest_tpu.serve.wsgi import App, run_with_graceful_shutdown

bus = make_bus(os.environ.get("REDIS_URL"))
app = App()


@app.route("/up")
def up(request):
    return Response(b"OK", mimetype="text/html")


@app.route("/api/health")
def health(request):
    return {"checks": {"model": {"status": "ok"}}}, 200


@app.route("/api/version")
def version(request):
    return {"version_label": os.environ.get("RTPU_VERSION"),
            "model": {"generation": 0}}, 200


@app.route("/api/realtime_feed")
def feed(request):
    channel = request.args.get("channel", "sse")
    raw = (request.headers.get("Last-Event-ID")
           or request.args.get("last_event_id"))
    last_id = None
    if raw:
        try:
            last_id = int(raw)
        except ValueError:
            last_id = None
    sub = bus.subscribe(channel, last_event_id=last_id)
    return Response(sse_stream(sub), mimetype="text/event-stream",
                    headers={"Cache-Control": "no-cache",
                             "X-Accel-Buffering": "no"})


run_with_graceful_shutdown(app, "127.0.0.1", int(os.environ["PORT"]),
                           drain_timeout_s=5.0)
"""


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _ResumingSseClient:
    """An EventSource-shaped subscriber: reads ``id:``/``data:`` lines,
    and on ANY disconnect reconnects through the gateway with
    ``Last-Event-ID`` — the replay resume a browser does for free."""

    def __init__(self, base: str, channel: str) -> None:
        parts = urllib.parse.urlsplit(base)
        self.host, self.port = parts.hostname, parts.port
        self.path = f"/api/realtime_feed?channel={channel}"
        # Resume from the beginning on the FIRST connect too: events
        # published in the instant before the subscription lands replay
        # from the broker ring instead of racing it.
        self.last_id = 0
        self.seqs = []
        self.reconnects = -1          # first connect is not a REconnect
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self) -> None:
        while not self._stop.is_set():
            conn = http.client.HTTPConnection(self.host, self.port,
                                              timeout=10)
            try:
                headers = {}
                if self.last_id is not None:
                    headers["Last-Event-ID"] = str(self.last_id)
                conn.request("GET", self.path, headers=headers)
                resp = conn.getresponse()
                if resp.status != 200:
                    time.sleep(0.1)
                    continue
                self.reconnects += 1
                sock = conn.sock or getattr(
                    getattr(resp.fp, "raw", None), "_sock", None)
                if sock is not None:
                    sock.settimeout(1.0)
                buf = b""
                while not self._stop.is_set():
                    try:
                        chunk = resp.read1(65536)
                    except (TimeoutError, socket.timeout):
                        break     # idle poison (see loadgen) — reconnect
                    if not chunk:
                        break     # replica drained away: resume
                    buf += chunk
                    *lines, buf = buf.split(b"\n")
                    for line in lines:
                        if line.startswith(b"id: "):
                            self.last_id = int(line[4:])
                        elif line.startswith(b"data: "):
                            self.seqs.append(
                                json.loads(line[6:])["seq"])
            except (http.client.HTTPException, OSError):
                time.sleep(0.05)
            finally:
                conn.close()

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)


def test_rolling_restart_with_live_sse_zero_dropped_events():
    broker, _ = start_broker()
    env = dict(os.environ)
    env["REDIS_URL"] = f"tcp://127.0.0.1:{broker.port}"
    ports = [_free_port(), _free_port()]
    sup = ReplicaSupervisor(
        ports, command=lambda p: [sys.executable, "-c", _SSE_WORKER],
        env=env, probe_interval_s=0.2, backoff_base_s=0.2,
        backoff_cap_s=1.0)
    gw = None
    publish_stop = threading.Event()
    try:
        sup.start()
        assert sup.ready(timeout=60)
        gw = Gateway([("127.0.0.1", p) for p in ports],
                     FleetConfig(hedge=False), supervisor=sup)
        httpd = gw.serve("127.0.0.1", 0)
        base = f"http://127.0.0.1:{httpd.server_address[1]}"

        bus = NetBus(env["REDIS_URL"])
        published = 0

        def publish():
            # Until told to stop, and never further ahead of the
            # subscriber than half the broker's replay ring. A fixed
            # count ran out on a loaded host before the roll reached
            # the subscriber's replica (a resumed stream with nothing
            # left to send never answers, so no reconnect was seen),
            # and a free-running publisher outruns the ring whenever a
            # resume takes longer than the ring is long: both lose to
            # the host's speed, not to the roll.
            nonlocal published
            while not publish_stop.is_set():
                seen = client.seqs[-1] + 1 if client.seqs else 0
                if published - seen < Broker.HISTORY // 2:
                    bus.publish("roll", {"seq": published})
                    published += 1
                time.sleep(0.04)

        with _ResumingSseClient(base, "roll") as client:
            pub_thread = threading.Thread(target=publish, daemon=True)
            pub_thread.start()
            # Let the stream light up before the roll.
            deadline = time.time() + 20
            while time.time() < deadline and not client.seqs:
                time.sleep(0.05)
            assert client.seqs, "SSE stream never delivered"

            out = rolling_restart(
                sup, gw, version="v2-sse",
                env={"RTPU_VERSION": "v2-sse"}, max_unavailable=1,
                drain_timeout_s=2.0, boot_timeout_s=60.0,
                health_timeout_s=10.0)
            assert out["ok"], out
            assert len(out["replaced"]) == 2
            # Keep publishing until the resumed stream has delivered an
            # event published AFTER the roll — it is LIVE, not just
            # replayed — then stop and let the tail flush.
            live_from = published
            deadline = time.time() + 20
            while time.time() < deadline and not (
                    client.seqs and client.seqs[-1] > live_from):
                time.sleep(0.05)
            assert client.seqs[-1] > live_from, \
                "the resumed stream delivered nothing published after the roll"
            publish_stop.set()
            pub_thread.join(timeout=10)
            deadline = time.time() + 20
            while time.time() < deadline \
                    and len(set(client.seqs)) < published:
                time.sleep(0.1)

        # Every replica is on the new version (the restart completed,
        # drains included — a stuck drain would have failed `out`).
        with gw._lock:
            assert all(r.version == "v2-sse" for r in gw.replicas)
        assert {s["version"] for s in sup.snapshot().values()} \
            == {"v2-sse"}
        # ZERO dropped events: the subscriber saw every published seq
        # exactly (duplicates from replay overlap are legal; gaps are
        # the bug).
        assert published > 50
        received = set(client.seqs)
        missing = [s for s in range(published) if s not in received]
        assert not missing, f"dropped {len(missing)} events: " \
                            f"{missing[:10]} (of {published})"
        # The stream actually rode through ≥1 reconnect (the roll cut
        # its replica) — otherwise this test proved nothing.
        assert client.reconnects >= 1
    finally:
        publish_stop.set()
        if gw is not None:
            gw.drain(timeout=5)
        sup.drain(timeout=15)
        broker.shutdown()
