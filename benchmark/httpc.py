"""A small JSON-over-HTTP client on the standard library: one keep-alive
connection, the `X-Nomad-Index` header returned beside the body. The load
generator's process uses nothing else to reach the server, so it never
imports the program or JAX."""

from __future__ import annotations

import http.client
import json
import urllib.parse


class HTTPStatusError(Exception):
    def __init__(self, status: int, body: bytes):
        super().__init__(f"HTTP {status}: {body[:200]!r}")
        self.status = status


class Conn:
    def __init__(self, addr: str, timeout: float = 120.0):
        parsed = urllib.parse.urlparse(addr)
        self.host, self.port, self.timeout = parsed.hostname, parsed.port, timeout
        self._conn = None

    def close(self) -> None:
        if self._conn is not None:
            self._conn.close()
            self._conn = None

    def request_raw(self, method: str, path: str, body: bytes = None):
        """(payload bytes, index). One reconnect if the kept-alive
        socket was closed under us before anything was sent back."""
        headers = {"Content-Type": "application/json"} if body else {}
        for attempt in (0, 1):
            if self._conn is None:
                self._conn = http.client.HTTPConnection(
                    self.host, self.port, timeout=self.timeout)
            try:
                self._conn.request(method, path, body=body, headers=headers)
                resp = self._conn.getresponse()
                payload = resp.read()
            except (http.client.RemoteDisconnected, ConnectionResetError,
                    BrokenPipeError):
                self.close()
                if attempt:
                    raise
                continue
            if resp.status >= 400:
                raise HTTPStatusError(resp.status, payload)
            return payload, int(resp.getheader("X-Nomad-Index") or 0)

    def request(self, method: str, path: str, body: bytes = None):
        payload, index = self.request_raw(method, path, body)
        return json.loads(payload or b"null"), index
