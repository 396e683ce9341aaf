"""What decides ``correct``: the timed path's own outputs against the
float32 reference of the configuration's block.

Once the window has closed and its requests have finished, the client
keeps serving the mix and ``Capture`` intercepts the compiled programs
that ``JaxBackend.execute`` calls, without changing them: a few prefill
calls, then ``K`` consecutive decode calls of the full device batch.
Around each decode call it keeps, for every array leaf of the cache, the
slot each row writes (before and after) and a fingerprint of every other
slot. A leaf of the stacked layers is (L, B, T, ...), and one of an
unrolled prefix layer (B, T, ...) is taken as L = 1. The reference that
the configuration names (``spec.Bench.reference``) then re-computes
those calls from the same inputs.

The numbers compared, each against its limit:

- ``decode_logit_gap``: over every served row of every captured decode
  call, the widest gap by which the reference logit of the token the
  program ranks first lies below the reference's best logit.
- ``decode_logit_rel``, ``prefill_logit_rel``: the relative L2 gap of the
  served rows' logits, and of the captured prefill calls' logits.
- ``cache_write_gap``: over the captured calls, the layers and the
  cache's leaves, the largest relative L2 gap between what the program
  wrote to the rows' slots and the reference's.

A configuration compares the numbers it has limits for
(``check_limits``), and always the exact ones:

- ``cache_other_slots_changed``: (call, leaf, layer, row) entries whose
  cache changed outside the written slot.
- ``requests_wrong_length``: requests due in the window that did not
  finish with exactly their output length.
- ``window_compiles``: compilations from the ramp to the window's close.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

#: served tokens to compare at least, over the captured decode calls
DECODE_TOKENS = 256
#: prefill calls to capture (of two tokens or more)
PREFILL_CALLS = 2

EXACT = ("cache_other_slots_changed", "requests_wrong_length",
         "window_compiles")


def _stacked(tree) -> list:
    """Every array leaf of a tree in the decoder cache's structure with
    its layer axis first: the unrolled prefix layers' leaves as L = 1,
    then the stacked layers'. Of the cache itself, (L, B, T, ...)."""
    return ([a[None] for a in jax.tree.leaves(tree["prefix"])]
            + jax.tree.leaves(tree["scanned"]))


def _by_calls(slots: List[dict]):
    """Each call's slots (the cache's structure, leaves (L, B, ...) or a
    prefix layer's (B, ...)) as one tree with the calls after L."""
    return {"prefix": jax.tree.map(lambda *a: jnp.stack(a),
                                   *[c["prefix"] for c in slots]),
            "scanned": jax.tree.map(lambda *a: jnp.stack(a, axis=1),
                                    *[c["scanned"] for c in slots])}


@jax.jit
def _slots(cache, slot):
    """The slot each row writes, in the cache's structure: (L, B, ...)
    of a stacked leaf, (B, ...) of a prefix layer's."""
    rows = jnp.arange(slot.shape[0])
    return {"prefix": jax.tree.map(lambda a: a[rows, slot],
                                   cache["prefix"]),
            "scanned": jax.tree.map(lambda a: a[:, rows, slot],
                                    cache["scanned"])}


_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


@jax.jit
def _fingerprint(cache, slot):
    """(L, B) position-weighted sums of the bits of every slot but the
    written one, for each leaf of ``_stacked``: equal before and after
    exactly when nothing else changed (up to a collision)."""
    def fp(a):
        bits = jax.lax.bitcast_convert_type(
            a, _UINT[a.dtype.itemsize]).astype(jnp.uint32)
        t = a.shape[2]
        w = (jnp.arange(bits[0, 0].size, dtype=jnp.uint32)
             * jnp.uint32(2654435761) + 1).reshape(bits.shape[2:])
        keep = jnp.arange(t)[None, :] != slot[:, None]          # (B, T)
        keep = keep.reshape(keep.shape + (1,) * (a.ndim - 3))
        return jnp.sum(bits * w * keep[None], axis=tuple(range(2, a.ndim)),
                       dtype=jnp.uint32)
    return [fp(a) for a in _stacked(cache)]


class Capture:
    """Intercepts the backend's compiled decode and prefill programs."""

    def __init__(self, backend):
        self.backend = backend
        self.k_calls = -(-DECODE_TOKENS // backend.max_batch)
        self.decode: List[dict] = []
        self.prefill: List[dict] = []
        self.final = None
        self._plan = None
        self._real_execute = backend.execute
        self._real_decode = backend._decode
        self._real_prefill = dict(backend._prefill)

    @property
    def done(self) -> bool:
        return (len(self.decode) >= self.k_calls
                and len(self.prefill) >= PREFILL_CALLS)

    def install(self) -> None:
        b = self.backend

        def execute(plan, f_mhz):
            self._plan = plan
            return self._real_execute(plan, f_mhz)

        def prefill_fn(fn):
            def run(params, toks):
                out = fn(params, toks)
                keep = len(self.prefill) < PREFILL_CALLS
                if keep and toks.shape[1] > 1:
                    self.prefill.append({"tokens": np.asarray(toks)[0],
                                         "logits": out[0]})
                return out
            return run

        b.execute = execute
        b._prefill = {n: (prefill_fn(fn), toks)
                      for n, (fn, toks) in self._real_prefill.items()}

    def install_decode(self) -> None:
        """From the next decode call on, keep ``k_calls`` consecutive
        calls; prefill calls are captured first, so that nothing runs
        after the last kept decode call."""
        t = self.backend.cache_len

        def decode(params, token, cache, pos):
            if len(self.decode) >= self.k_calls:
                return self._real_decode(params, token, cache, pos)
            slot = jnp.asarray(np.minimum(pos, t - 1))
            fb = _fingerprint(cache, slot)
            old = _slots(cache, slot)
            logits, new = self._real_decode(params, token, cache, pos)
            self.decode.append({
                "token": np.asarray(token)[:, 0], "pos": np.asarray(pos),
                "served": len(self._plan.decode), "logits": logits[:, 0],
                "old": old, "new": _slots(new, slot),
                "fp_before": fb, "fp_after": _fingerprint(new, slot)})
            if len(self.decode) == self.k_calls:
                self.final = new
            return logits, new

        self.backend._decode = decode

    def uninstall(self) -> None:
        b = self.backend
        b.execute = self._real_execute
        b._decode = self._real_decode
        b._prefill = self._real_prefill


def _gap(ref, served):
    """Widest gap, over rows, between the reference's best logit and its
    logit of the token that ``served`` ranks first."""
    ref = jnp.asarray(ref, jnp.float32)
    top = jnp.argmax(jnp.asarray(served, jnp.float32), axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, top[..., None], axis=-1)[..., 0]
    return best - got


def _rel(a, b, axes):
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    return (jnp.sqrt(jnp.sum((a - b) ** 2, axes))
            / jnp.sqrt(jnp.sum(b * b, axes)))


def readings(cap: Capture, weights, conf: dict, reference,
             control: bool = False) -> Dict[str, float]:
    """The compared numbers of the captured calls, against ``reference``
    (the configuration's module, ``spec.Bench.reference``). With
    ``control``, the reference's control (the precision below the served
    one) takes the program's place and is read the same way."""
    d = cap.decode
    tokens = np.stack([c["token"] for c in d])
    pos = np.stack([c["pos"] for c in d])
    old = _by_calls([c["old"] for c in d])
    ref, rw = reference.decode(weights, conf, tokens, cap.final, pos, old)
    if control:
        served, written = reference.decode(weights, conf, tokens, cap.final,
                                           pos, old, control=True)
    else:
        served = jnp.stack([c["logits"] for c in d])
        written = _by_calls([c["new"] for c in d])
    rows = np.arange(pos.shape[1])[None, :] < np.array(
        [c["served"] for c in d])[:, None]
    dgap = _gap(ref, served)
    # each leaf's (L, K) relative gaps over each call's rows
    wgap = max(float(jnp.max(_rel(w, r, tuple(range(2, w.ndim)))))
               for w, r in zip(_stacked(written), _stacked(rw)))
    prels = []
    for c in cap.prefill:
        pref = reference.prefill(weights, conf, c["tokens"])
        pserved = (reference.prefill(weights, conf, c["tokens"],
                                     control=True)
                   if control else c["logits"])
        prels.append(float(_rel(pserved, pref, None)))
    changed = sum(int(np.sum(np.asarray(b) != np.asarray(a)))
                  for c in d for b, a in zip(c["fp_before"], c["fp_after"]))
    sr = np.nonzero(rows)
    return {
        "decode_logit_gap": float(jnp.max(dgap[sr])),
        "decode_logit_rel": float(_rel(jnp.asarray(served)[sr], ref[sr],
                                       None)),
        "prefill_logit_rel": max(prels),
        "cache_write_gap": wgap,
        "cache_other_slots_changed": 0 if control else changed,
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]
            ) -> Dict[str, dict]:
    """Each compared number beside its limit; exact ones have limit 0."""
    out = {}
    for name, value in numbers.items():
        if name in EXACT:
            out[name] = {"value": value, "limit": 0}
        elif name in limits:
            out[name] = {"value": value, "limit": limits[name]}
    return out


def passed(checks: Dict[str, dict]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
