"""One ``POST /query`` over a fresh loopback connection, and the
percentile arithmetic the end-to-end metrics use."""
from __future__ import annotations

import http.client
import json
import time
from typing import Dict, List, Optional

import numpy as np

TIMEOUT_S = 300.0


def post(port: int, body: Dict, record: Dict) -> Dict:
    """Fill ``record`` with ``sent``, ``done`` (perf_counter seconds),
    ``status`` and, on success, ``ids``, ``scores``, ``trace_id``."""
    record["sent"] = time.perf_counter()
    try:
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=TIMEOUT_S)
        try:
            conn.request("POST", "/query", json.dumps(body),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            status, payload = resp.status, json.loads(resp.read())
        finally:
            conn.close()
    except (OSError, http.client.HTTPException, ValueError) as e:
        status, payload = 0, {"error": repr(e)}
    record["done"] = time.perf_counter()
    record["status"] = status
    record["ok"] = status == 200 and bool(payload.get("ok"))
    if record["ok"]:
        record["ids"] = payload["ids"]
        record["scores"] = payload["scores"]
        record["trace_id"] = payload.get("trace_id")
    else:
        record["error"] = payload.get("error")
    return record


def latency_s(rec: Dict) -> float:
    """Due to answered. A request that failed or never came counts as the
    client's whole timeout: it misses any limit."""
    if not rec.get("ok"):
        return TIMEOUT_S
    return rec["done"] - rec["due"]


def percentile_ms(values: List[float], q: float) -> Optional[float]:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q)) * 1e3
