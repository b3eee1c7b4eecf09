"""Batched set-full analysis kernel (BASELINE config 4).

The reference's set-full checker (jepsen/src/jepsen/checker.clj:294-592)
walks a per-element state machine over every read. Here the whole
history becomes one dense boolean *membership matrix* ``member[R, E]``
(reads x interned elements) plus three time vectors, and every
element's verdict — stable / lost / never-read, plus stale-read
detection and stable-visibility latency — is a handful of masked
row-reductions over the matrix, computed for all elements at once on
device. Rows are the TPU-friendly axis: R and E are padded to bucketed
shapes so XLA caches one program per bucket, and the element axis can
be sharded over a mesh (each shard reduces its own columns; no
cross-device traffic).

Verdict codes: 0 = stable, 1 = lost, 2 = never-read.
"""
from __future__ import annotations

import numpy as np

STABLE, LOST, NEVER_READ = 0, 1, 2

_NEG = np.float32(-3.4e38)
_POS = np.float32(3.4e38)


def _build_classify(R: int, E: int):
    import jax.numpy as jnp

    def classify(member, t_read, read_valid, invoke_t, ok_t, has_ok, el_valid):
        """member: bool[R, E]; t_read: f32[R]; read_valid: bool[R];
        invoke_t/ok_t: f32[E]; has_ok/el_valid: bool[E].

        Returns (code i32[E], stale bool[E], latency f32[E]) — latency is
        meaningful only where code == STABLE.
        """
        m = member & read_valid[:, None]                      # [R, E]
        seen_t = jnp.where(m, t_read[:, None], _POS)
        first_seen = seen_t.min(axis=0)                       # +inf if never
        # known time: add-ok time, else first sighting
        known = jnp.where(has_ok, ok_t, first_seen)           # [E]
        never_known = known >= _POS

        later = read_valid[:, None] & (t_read[:, None] >= known[None, :])
        any_later = later.any(axis=0)

        lp = jnp.where(later & member, t_read[:, None], _NEG).max(axis=0)
        la = jnp.where(later & ~member, t_read[:, None], _NEG).max(axis=0)
        has_present = lp > _NEG
        has_absent = la > _NEG

        lost = has_absent & (~has_present | (la > lp))
        never_read = never_known | ~any_later
        code = jnp.where(never_read, NEVER_READ,
                         jnp.where(lost, LOST, STABLE)).astype(jnp.int32)
        # stale: absent after known, but present again later (only
        # meaningful for stable elements)
        stale = (code == STABLE) & has_absent
        stable_from = jnp.where(has_absent, la, known)
        latency = jnp.maximum(0.0, stable_from - invoke_t)
        code = jnp.where(el_valid, code, NEVER_READ)
        return code, stale & el_valid, latency

    return classify


_JIT_CACHE: dict = {}


def _bucketed(n: int, floor: int = 64) -> int:
    from jepsen_tpu.ops.jitlin import _bucket
    return _bucket(n, floor=floor)


def classify_elements(member: np.ndarray, t_read: np.ndarray,
                      invoke_t: np.ndarray, ok_t: np.ndarray,
                      has_ok: np.ndarray):
    """Pads to bucketed [R, E] shapes and runs the device kernel.
    Returns (code[E], stale[E], latency[E]) numpy arrays."""
    import jax
    import jax.numpy as jnp

    R, E = member.shape
    Rb, Eb = _bucketed(max(R, 1)), _bucketed(max(E, 1))
    key = (Rb, Eb)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        base = _build_classify(Rb, Eb)

        def unpack_and_classify(packed, *rest):
            # bit-unpack on device: the [R, E] membership matrix ships
            # as uint8 bits (8x less host->device traffic — the matrix
            # is the whole transfer cost)
            bits = (packed[:, :, None]
                    >> jnp.arange(8, dtype=jnp.uint8)) & 1
            m = bits.reshape(Rb, -1)[:, :Eb].astype(bool)
            return base(m, *rest)

        fn = jax.jit(unpack_and_classify)
        _JIT_CACHE[key] = fn

    mem = np.zeros((Rb, Eb), dtype=bool)
    mem[:R, :E] = member
    mem = np.packbits(mem, axis=1, bitorder="little")
    tr = np.full((Rb,), _POS, dtype=np.float32)
    tr[:R] = t_read
    rv = np.zeros((Rb,), dtype=bool)
    rv[:R] = True
    iv = np.zeros((Eb,), dtype=np.float32)
    iv[:E] = invoke_t
    okt = np.full((Eb,), _POS, dtype=np.float32)
    okt[:E] = ok_t
    hok = np.zeros((Eb,), dtype=bool)
    hok[:E] = has_ok
    ev = np.zeros((Eb,), dtype=bool)
    ev[:E] = True

    code, stale, latency = fn(jnp.asarray(mem), jnp.asarray(tr),
                              jnp.asarray(rv), jnp.asarray(iv),
                              jnp.asarray(okt), jnp.asarray(hok),
                              jnp.asarray(ev))
    # one batched host transfer (three sequential syncs would pay a
    # device round-trip each)
    code, stale, latency = jax.device_get((code, stale, latency))
    return code[:E], stale[:E], latency[:E]
