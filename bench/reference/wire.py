"""Plain decoder of the self-describing wire payload, written from its
format alone (it imports nothing of the system under test).

Layout: ``b"WP" | version u8 | spec_len u16 | spec | body dtype u8 |
n_stages u8 | per stage: params_len u32, params | body``; integers in the
header are big-endian.  Stages decode last to first:

* ``int8(block)``: params ``n u64 | block u32 | scales <f4``; the body holds
  ``ceil(n / block) * block`` int8 codes and the value is ``code * scale``
  of its block, truncated to ``n``;
* ``topk(f)``: params ``n u64 | indices <u4``; the flowing values land at
  the indices of a zero vector of length ``n``;
* ``delta`` and ``ef``: identity on decode.

Encoding (:func:`encode`), from the stages' definitions: ``delta`` sends
``vec - ref`` (the model the sender trained from); ``ef`` adds the
sender's residual and keeps, as the next residual, what the stages after
it lost (``compensated - sent``); ``topk(f)`` keeps the
``k = min(n, max(1, int(n * f)))`` entries of largest magnitude, in index
order; ``int8(block)`` scales each block of ``block`` values by its
largest magnitude (at least 1e-12) over 127 and rounds half to even,
clipped to +-127.
"""

from __future__ import annotations

import struct
from typing import Optional

import numpy as np

_DTYPES = ("<f4", "i1", "u1", "<u4")


def parse(data: bytes) -> tuple[list[str], list[bytes], np.ndarray]:
    """(stage names, stage params, body array) of one payload."""
    if data[:2] != b"WP":
        raise ValueError("not a wire payload")
    (spec_len,) = struct.unpack_from("!H", data, 3)
    off = 5
    spec = data[off:off + spec_len].decode("utf-8")
    off += spec_len
    dtype, n_stages = data[off], data[off + 1]
    off += 2
    params = []
    for _ in range(n_stages):
        (plen,) = struct.unpack_from("!I", data, off)
        off += 4
        params.append(bytes(data[off:off + plen]))
        off += plen
    stages = [tok.split("(")[0] for tok in spec.split("|")]
    return stages, params, np.frombuffer(data, _DTYPES[dtype], offset=off)


def decode(data: bytes) -> np.ndarray:
    """One payload -> its flat float32 vector."""
    stages, params, arr = parse(data)
    for name, p in zip(reversed(stages), reversed(params)):
        if name == "int8":
            n, block = struct.unpack_from("!QI", p, 0)
            scales = np.frombuffer(p, "<f4", offset=12)
            q = arr.astype(np.float32).reshape(scales.size, block)
            arr = (q * scales[:, None]).reshape(-1)[:n]
        elif name == "topk":
            (n,) = struct.unpack_from("!Q", p, 0)
            idx = np.frombuffer(p, "<u4", offset=8)
            out = np.zeros(n, np.float32)
            out[idx] = arr
            arr = out
        elif name not in ("delta", "ef"):
            raise ValueError(f"no reference decode for stage {name!r}")
    return np.asarray(arr, np.float32)


def work(data: bytes) -> list[tuple[str, dict]]:
    """The decode stages' sizes for one payload, in decode order: what the
    roofline functions count (``bench/fedbench/roofline.py``)."""
    stages, params, arr = parse(data)
    out = []
    count = arr.size
    for name, p in zip(reversed(stages), reversed(params)):
        if name == "int8":
            n, block = struct.unpack_from("!QI", p, 0)
            out.append(("int8", {"blocks": (len(p) - 12) // 4,
                                 "block": block, "n": n}))
            count = n
        elif name == "topk":
            (n,) = struct.unpack_from("!Q", p, 0)
            out.append(("topk", {"k": count, "n": n}))
            count = n
    return out


def parse_spec(spec: str) -> list[tuple[str, str]]:
    """``"delta|ef|topk(0.01)|int8(1024)"`` -> [(name, argument), ...]."""
    out = []
    for tok in spec.split("|"):
        name, _, arg = tok.partition("(")
        out.append((name, arg.rstrip(")")))
    return out


def topk_count(n: int, fraction: float) -> int:
    return min(n, max(1, int(n * fraction)))


def is_topk(x: np.ndarray, idx: np.ndarray, k: int) -> bool:
    """Whether ``idx`` names ``k`` distinct entries of largest magnitude
    of ``x`` (so a different choice among equal magnitudes still holds)."""
    idx = np.asarray(idx, np.int64)
    if idx.size != k or np.unique(idx).size != k or (
            k and (idx.min() < 0 or idx.max() >= x.size)):
        return False
    if k == 0 or k == x.size:
        return True
    mag = np.abs(x)
    rest = np.ones(x.size, bool)
    rest[idx] = False
    return bool(mag[idx].min() >= mag[rest].max())


def int8_roundtrip(x: np.ndarray, block: int) -> np.ndarray:
    """The values an ``int8(block)`` stage delivers for ``x``."""
    x = np.asarray(x, np.float32)
    nb = -(-x.size // block)
    pad = np.zeros(nb * block, np.float32)
    pad[:x.size] = x
    blocks = pad.reshape(nb, block)
    scales = (np.maximum(np.abs(blocks).max(axis=1), np.float32(1e-12))
              / np.float32(127.0)).astype(np.float32)
    q = np.clip(np.rint(blocks / scales[:, None]), -127, 127)
    return (q.astype(np.float32) * scales[:, None]).reshape(-1)[:x.size]


def encode(vec: np.ndarray, spec: str, ref=None, residual=None,
           kept=None) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """What the pipeline ``spec`` delivers for ``vec``, as the receiver
    decodes it, and the residual its ``ef`` stage keeps (None without
    one).  ``kept``: the indices the sender's top-k chose; they are used
    where they are a valid choice (:func:`is_topk`), so that ties between
    equal magnitudes are not read as a fault."""
    stages = parse_spec(spec)
    x = np.asarray(vec, np.float32)
    comp = None
    for i, (name, arg) in enumerate(stages):
        if name == "delta":
            if ref is not None:
                x = x - np.asarray(ref, np.float32)
        elif name == "ef":
            if residual is not None:
                x = x + np.asarray(residual, np.float32)
            comp = x
            sent = _tail(x, stages[i + 1:], kept)
            return sent, comp - sent
        else:
            return _tail(x, stages[i:], kept), None
    return x, None


def _tail(x: np.ndarray, stages, kept) -> np.ndarray:
    """Encode then decode ``x`` through lossy stages (topk, int8)."""
    idx = None
    for name, arg in stages:
        if name == "topk":
            k = topk_count(x.size, float(arg))
            if kept is not None and is_topk(x, kept, k):
                idx = np.sort(np.asarray(kept, np.int64))
            else:
                idx = np.sort(np.argsort(-np.abs(x), kind="stable")[:k])
            n, x = x.size, x[idx]
        elif name == "int8":
            x = int8_roundtrip(x, int(arg))
        else:
            raise ValueError(f"no reference encode for stage {name!r}")
    if idx is None:
        return x
    out = np.zeros(n, np.float32)
    out[idx] = x
    return out


def topk_indices(data: bytes) -> Optional[np.ndarray]:
    """The indices a payload's top-k stage kept, or None without one."""
    stages, params, _ = parse(data)
    for name, p in zip(stages, params):
        if name == "topk":
            return np.frombuffer(p, "<u4", offset=8).astype(np.int64)
    return None
