# A copy of `opensim_tpu/server/stubapi.py`, byte for byte below this comment.
# It is the far end of the traffic (the apiserver the twin syncs from), not the
# system under test, so it is part of the yardstick and lives under `paths`,
# where a later PR cannot change it; the original stays for tests/ and make
# twin-smoke (PERF.md, open questions).
"""Canned stub apiserver speaking list + watch — the chaos harness's fake
cluster (tests/test_watch.py, ``make twin-smoke``).

Just enough of the kube API machinery to prove the live twin's failure
surface deterministically, with no kubernetes package and no real cluster:

- ``GET <path>?resourceVersion=0`` — ``kind: List`` JSON with a list-level
  ``metadata.resourceVersion`` (a process-global counter, monotonically
  bumped by every mutation, like etcd's revision);
- ``GET <path>?watch=1&resourceVersion=<rv>`` — a line-delimited JSON event
  stream (``{"type": "ADDED"|"MODIFIED"|"DELETED"|"BOOKMARK", "object":
  …}``), replaying retained events past ``rv`` and then following live
  mutations, with BOOKMARK keepalives while idle;
- **410 Gone** — :meth:`StubApiServer.compact` discards the retained event
  log (etcd compaction); a watch asking for an rv behind the compaction
  floor gets the mid-stream ``ERROR`` event with ``code: 410``;
- **server-side drops** — :meth:`StubApiServer.force_disconnect` severs
  every open watch connection (LB idle reset, apiserver rolling restart);
- **RBAC shaping** — :attr:`StubApiServer.forbidden_paths` returns 403 for
  chosen endpoints (minimal-RBAC clusters).

Mutations (:meth:`upsert` / :meth:`delete`) assign object resourceVersions
and notify watchers; :meth:`kubeconfig` writes a bearer-token kubeconfig
pointing at the server, so the whole stdlib REST + watch ladder runs
end-to-end against it.
"""

from __future__ import annotations

import json
import threading
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Tuple


def _key(obj: dict) -> Tuple[str, str]:
    meta = obj.get("metadata") or {}
    return (str(meta.get("namespace") or ""), str(meta.get("name") or ""))


class StubApiServer:
    def __init__(self, bookmark_interval_s: float = 0.2) -> None:
        self.bookmark_interval_s = bookmark_interval_s
        self._cond = threading.Condition()
        self._rv = 1000
        self._stores: Dict[str, "dict[Tuple[str, str], dict]"] = {}
        self._events: List[Tuple[int, str, str, dict]] = []  # (rv, path, type, obj)
        self._compacted_rv = 0
        self._disconnect_epoch = 0
        self.forbidden_paths: set = set()
        #: every GET as (path, {param: [values]}) — tests assert on the
        #: query contract (resourceVersion=0 lists, watch resumption rvs)
        self.requests_seen: List[Tuple[str, dict]] = []
        self._httpd: Optional[ThreadingHTTPServer] = None

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "StubApiServer":
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # noqa: N802 (stdlib name)
                pass

            def do_GET(self):  # noqa: N802
                stub._handle(self)

        self._httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        threading.Thread(target=self._httpd.serve_forever, daemon=True).start()
        return self

    def stop(self) -> None:
        self.force_disconnect()
        if self._httpd is not None:
            self._httpd.shutdown()

    @property
    def url(self) -> str:
        assert self._httpd is not None, "call start() first"
        host, port = self._httpd.server_address[:2]
        return f"http://{host}:{port}"

    def kubeconfig(self, dirpath: str) -> str:
        """Write a bearer-token kubeconfig pointing at this stub; returns
        its path."""
        import os

        path = os.path.join(str(dirpath), "stub-kubeconfig")
        with open(path, "w") as f:
            f.write(
                "apiVersion: v1\nkind: Config\ncurrent-context: stub\n"
                "contexts:\n  - name: stub\n    context: {cluster: stub, user: stub}\n"
                f"clusters:\n  - name: stub\n    cluster: {{server: '{self.url}'}}\n"
                "users:\n  - name: stub\n    user: {token: stub-token}\n"
            )
        return path

    # -- mutation API --------------------------------------------------------

    def rv(self) -> int:
        with self._cond:
            return self._rv

    def seed(self, path: str, objs: List[dict]) -> None:
        """Install initial objects WITHOUT emitting watch events (they
        predate every watcher, like objects created before the server)."""
        with self._cond:
            store = self._stores.setdefault(path, {})
            for obj in objs:
                self._rv += 1
                obj = json.loads(json.dumps(obj))  # private copy
                obj.setdefault("metadata", {})["resourceVersion"] = str(self._rv)
                store[_key(obj)] = obj

    def upsert(self, path: str, obj: dict, ev_type: Optional[str] = None) -> int:
        """Create/replace an object; emits ADDED or MODIFIED (or a forced
        ``ev_type`` — chaos tests use this to send duplicates and other
        malformed sequences). Returns the assigned resourceVersion."""
        with self._cond:
            store = self._stores.setdefault(path, {})
            k = _key(obj)
            self._rv += 1
            obj = json.loads(json.dumps(obj))
            obj.setdefault("metadata", {})["resourceVersion"] = str(self._rv)
            kind = ev_type or ("MODIFIED" if k in store else "ADDED")
            store[k] = obj
            self._events.append((self._rv, path, kind, obj))
            self._cond.notify_all()
            return self._rv

    def delete(self, path: str, name: str, namespace: str = "default") -> Optional[int]:
        with self._cond:
            store = self._stores.setdefault(path, {})
            obj = store.pop((namespace, name), None)
            if obj is None:
                return None
            self._rv += 1
            obj = json.loads(json.dumps(obj))
            obj["metadata"]["resourceVersion"] = str(self._rv)  # final rv
            self._events.append((self._rv, path, "DELETED", obj))
            self._cond.notify_all()
            return self._rv

    def compact(self) -> None:
        """Discard the retained event log (etcd compaction): any watch
        resuming from an rv at or behind the floor now gets 410 Gone."""
        with self._cond:
            self._compacted_rv = self._rv
            self._events.clear()
            self._cond.notify_all()

    def force_disconnect(self) -> None:
        """Sever every open watch connection server-side."""
        with self._cond:
            self._disconnect_epoch += 1
            self._cond.notify_all()

    # -- HTTP ----------------------------------------------------------------

    def _handle(self, h: BaseHTTPRequestHandler) -> None:
        path, _, query = h.path.partition("?")
        params = urllib.parse.parse_qs(query)
        with self._cond:
            self.requests_seen.append((path, params))
        if path in self.forbidden_paths:
            self._send_json(h, 403, {"kind": "Status", "code": 403, "reason": "Forbidden"})
            return
        if path not in self._stores:
            self._send_json(h, 404, {"kind": "Status", "code": 404, "reason": "NotFound"})
            return
        if params.get("watch") == ["1"]:
            try:
                rv = int((params.get("resourceVersion") or ["0"])[0] or 0)
            except ValueError:
                rv = 0
            self._serve_watch(h, path, rv)
            return
        with self._cond:
            items = [json.loads(json.dumps(o)) for o in self._stores[path].values()]
            rv_now = self._rv
        self._send_json(
            h, 200,
            {"kind": "List", "metadata": {"resourceVersion": str(rv_now)}, "items": items},
        )

    def _send_json(self, h: BaseHTTPRequestHandler, code: int, body: dict) -> None:
        data = json.dumps(body).encode()
        h.send_response(code)
        h.send_header("Content-Type", "application/json")
        h.send_header("Content-Length", str(len(data)))
        h.end_headers()
        h.wfile.write(data)

    def _serve_watch(self, h: BaseHTTPRequestHandler, path: str, rv: int) -> None:
        h.send_response(200)
        h.send_header("Content-Type", "application/json")
        h.end_headers()

        def emit(ev: dict) -> bool:
            try:
                h.wfile.write(json.dumps(ev).encode() + b"\n")
                h.wfile.flush()
                return True
            except (BrokenPipeError, ConnectionResetError, OSError):
                return False

        with self._cond:
            epoch = self._disconnect_epoch
            expired = bool(rv) and rv < self._compacted_rv
            floor = self._compacted_rv
        if expired:
            emit(
                {
                    "type": "ERROR",
                    "object": {
                        "kind": "Status", "code": 410, "reason": "Expired",
                        "message": f"too old resource version: {rv} ({floor})",
                    },
                }
            )
            return
        cursor = rv
        while True:
            with self._cond:
                if self._disconnect_epoch != epoch:
                    return  # server-side drop: close the connection
                batch = [
                    (erv, etype, obj)
                    for erv, epath, etype, obj in self._events
                    if epath == path and erv > cursor
                ]
                if not batch:
                    self._cond.wait(self.bookmark_interval_s)
                    if self._disconnect_epoch != epoch:
                        return
                    batch = [
                        (erv, etype, obj)
                        for erv, epath, etype, obj in self._events
                        if epath == path and erv > cursor
                    ]
                    if not batch:
                        # idle: BOOKMARK keepalive carrying the current rv
                        bookmark_rv = self._rv
                        batch = [
                            (
                                cursor,
                                "BOOKMARK",
                                {"kind": "Bookmark",
                                 "metadata": {"resourceVersion": str(bookmark_rv)}},
                            )
                        ]
            for erv, etype, obj in batch:
                if not emit({"type": etype, "object": obj}):
                    return
                cursor = max(cursor, erv)
