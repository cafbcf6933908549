"""Readers of one class of operations in a window that mixes classes
(``ctx["ops"]``: the harness's own record of every timed operation,
its class as the verb gave it): the class's byte rate over the whole
window, as the end-to-end rates are taken (the bytes of operations that
ended inside the window over its seconds), and its tail, the harness's own nearest
rank over the operations that succeeded. None where the window holds no
operation of the class."""

from __future__ import annotations

from worker import percentile


def rate_mbps(ctx, cls: str):
    inside = [o for o in ctx["ops"]
              if o.cls == cls and o.ok and o.end <= ctx["t_close"]]
    if not inside:
        return None
    return sum(o.nbytes for o in inside) / 1e6 / (
        ctx["t_close"] - ctx["t_open"])


def p95_ms(ctx, *classes: str):
    lat = [(o.end - o.start) * 1e3 for o in ctx["ops"]
           if o.cls in classes and o.ok]
    return percentile(lat, 0.95) if lat else None
