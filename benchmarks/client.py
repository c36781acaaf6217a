"""A thin keep-alive REST client: one ``http.client.HTTPConnection`` per
worker, the endpoints, bodies and headers the SDK (keto_tpu/httpclient.py)
sends. The SDK opens a connection per call; no production caller does, so the
benchmark does not time TCP set-up."""

from __future__ import annotations

import http.client
import re

_TOTAL = re.compile(r"total;dur=([0-9.]+)")


def server_total_ms(header):
    """The ``total`` entry of a ``Server-Timing`` header, or None."""
    m = _TOTAL.search(header or "")
    return float(m.group(1)) if m else None


class Conn:
    def __init__(self, host: str, port: int, timeout: float = 60.0):
        self._args = (host, port, timeout)
        self._conn = None
        self.reconnects = -1  # the first connect is not a reconnect

    def _connect(self):
        self.close()
        host, port, timeout = self._args
        self._conn = http.client.HTTPConnection(host, port, timeout=timeout)
        self._conn.connect()
        self.reconnects += 1

    def request(self, method: str, path: str, body: bytes | None = None):
        """``(status, body bytes, response headers)``. A connection the
        server closed is reopened once; that shows in ``reconnects``."""
        headers = {"Content-Type": "application/json"} if body is not None else {}
        for attempt in (0, 1):
            if self._conn is None:
                self._connect()
            try:
                self._conn.request(method, path, body=body, headers=headers)
                resp = self._conn.getresponse()
                raw = resp.read()
                if resp.will_close:
                    self.close()
                return resp.status, raw, resp.headers
            except (http.client.HTTPException, ConnectionError, BrokenPipeError):
                self.close()
                if attempt:
                    raise

    def close(self):
        if self._conn is not None:
            self._conn.close()
            self._conn = None
