"""The benchmark's wrappers on the encoder instance: what crosses the
``ChunkEncoder`` boundary, counted and timed from outside the program
(the client's write path and its read plans call through the instance,
so attributes set on it see every call). ``encode_into`` of the device
encoder lands in ``encode``, so wrapping ``encode`` counts each call
once. The XOR parity of xor2..xor9 goals crosses as ``xor_parity`` and
``xor_parity_into``; both are wrapped, and a call that one makes of
the other on its thread is counted once. In a traced run each call is
also a ``TraceAnnotation``, which puts ``bench.encode`` /
``bench.recover`` / ``bench.xor`` on the profiler's clock.

``control`` puts a broken guarantee in the encoder's place, to show
that the comparison fails (never set in a benchmark run):
  parity-short    the last parity part is stored as zeros: m - 1 valid
                  parity parts, so not any k of k + m read back; a xor
                  goal's one parity part likewise
  recover-approx  the last 64 KiB of every recovered part come back as
                  zeros: an approximate answer where it was exact
``fault`` alters one byte where it is produced (tests only):
  encode-flip, recover-flip
"""

from __future__ import annotations

import contextlib
import threading
import time

from dataclasses import dataclass

CONTROLS = ("parity-short", "recover-approx")
FAULTS = ("encode-flip", "recover-flip")


@dataclass(frozen=True)
class TapCounts:
    """The tap's calls as they stood at one moment."""

    encode_calls: tuple   # (k, m, rows, part bytes, seconds)
    recover_calls: tuple  # (k, m, rows used, wanted, part bytes, seconds)
    xor_calls: tuple = ()  # (parts in, part bytes, seconds)


class EncoderTap:
    def __init__(self, enc, annotate=None, control=None, fault=None):
        self.enc = enc
        self.annotate = annotate or (lambda _name: contextlib.nullcontext())
        self.control, self.fault = control, fault
        self.lock = threading.Lock()
        self.inside = threading.local()     # a xor call on this thread
        self.reset()
        self._encode, self._recover = enc.encode, enc.recover
        self._xor, self._xor_into = enc.xor_parity, enc.xor_parity_into
        enc.encode, enc.recover = self.encode, self.recover
        enc.xor_parity, enc.xor_parity_into = self.xor_parity, \
            self.xor_parity_into

    def reset(self) -> None:
        self.encode_calls = []      # (k, m, rows, part bytes, seconds)
        self.recover_calls = []     # (k, m, rows used, wanted, part bytes, seconds)
        self.xor_calls = []         # (parts in, part bytes, seconds)

    def snapshot(self) -> TapCounts:
        with self.lock:
            return TapCounts(tuple(self.encode_calls),
                             tuple(self.recover_calls),
                             tuple(self.xor_calls))

    def remove(self) -> None:
        del self.enc.encode, self.enc.recover
        del self.enc.xor_parity, self.enc.xor_parity_into

    def _xor_call(self, call, parts):
        if getattr(self.inside, "xor", False):
            return call()
        self.inside.xor = True
        try:
            t0 = time.perf_counter()
            with self.annotate("bench.xor"):
                out = call()
            dt = time.perf_counter() - t0
        finally:
            self.inside.xor = False
        with self.lock:
            self.xor_calls.append((len(parts), len(parts[0]), dt))
        return out

    def xor_parity(self, parts):
        out = self._xor_call(lambda: self._xor(parts), parts)
        return out * 0 if self.control == "parity-short" else out

    def xor_parity_into(self, parts, out):
        self._xor_call(lambda: self._xor_into(parts, out), parts)
        if self.control == "parity-short":
            out[...] = 0

    def encode(self, k, m, data_parts):
        rows = sum(1 for p in data_parts if p is not None)
        length = next(len(p) for p in data_parts if p is not None)
        t0 = time.perf_counter()
        with self.annotate("bench.encode"):
            out = self._encode(k, m, data_parts)
        dt = time.perf_counter() - t0
        with self.lock:
            self.encode_calls.append((k, m, rows, length, dt))
        if self.control == "parity-short":
            out = list(out)
            out[-1] = out[-1] * 0
        if self.fault == "encode-flip":
            out = list(out)
            out[0] = out[0].copy()
            out[0][0] ^= 1
        return out

    def recover(self, k, m, parts, wanted):
        rows = sum(1 for p in parts.values() if p is not None)
        t0 = time.perf_counter()
        with self.annotate("bench.recover"):
            out = self._recover(k, m, parts, wanted)
        dt = time.perf_counter() - t0
        length = len(next(iter(out.values())))
        with self.lock:
            self.recover_calls.append((k, m, min(rows, k), len(wanted),
                                       length, dt))
        if self.control == "recover-approx":
            out = {w: v.copy() for w, v in out.items()}
            for v in out.values():
                v[-65536:] = 0
        if self.fault == "recover-flip":
            out = {w: v.copy() for w, v in out.items()}
            next(iter(out.values()))[0] ^= 1
        return out
