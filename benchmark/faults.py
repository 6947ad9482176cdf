"""Faults planted in the timed path, and the control.

The driver's runs plant nothing (``none``). The others exist to show that
the correctness check fails when the served output is wrong:

- ``stale`` (the control): the record credits each AU to the capture
  before the one it came from, the stale answer that a pipeline which
  delivers one frame late would give.
- ``state_unchanged``: the encoder's reference picture stays the first
  one it made, so every P frame predicts from a picture the decoder no
  longer holds.
- ``half_batch``: every second capture is left out and the frame before
  it is encoded in its place.
- ``token_altered``: one byte in the middle of every eighth AU is
  flipped where the encoder hands the AU out.
- ``bf16_sad``: the Pallas ME kernel's per-MB SAD goes back to the one
  f32 MXU dot that v5e rounds to bf16 (PR 21's first chip run): other
  motion vectors, no crash. On the CPU the dot is exact and nothing
  changes, so only a chip run reads it.
"""

from __future__ import annotations

FAULTS = ("none", "stale", "state_unchanged", "half_batch", "token_altered",
          "bf16_sad")

# the exact two-half SAD in pallas_me._me_mc_kernel, and PR 21's rounded one
_EXACT_SAD = """        hi = jnp.right_shift(rs, 8).astype(jnp.float32).astype(jnp.bfloat16)
        lo = jnp.bitwise_and(rs, 255).astype(jnp.float32).astype(jnp.bfloat16)
        mbsum = 256.0 * jnp.dot(hi, m_bf, preferred_element_type=jnp.float32) \\
            + jnp.dot(lo, m_bf, preferred_element_type=jnp.float32)  # (G, 128)
"""
_ROUNDED_SAD = """        mbsum = jnp.dot(rs.astype(jnp.float32), m_ref[:],
                        preferred_element_type=jnp.float32)
"""


class Fault:
    def __init__(self, name: str = "none"):
        if name not in FAULTS:
            raise ValueError(f"unknown fault {name!r}, one of {FAULTS}")
        self.name = name
        self._prev_frame = None
        self._n_frames = 0
        self._n_aus = 0
        self._ref = None

    def plant(self) -> None:
        """Before the encoder is built: swap in a broken kernel."""
        if self.name != "bf16_sad":
            return
        import inspect

        from selkies_tpu.models.h264 import pallas_me

        src = inspect.getsource(pallas_me._me_mc_kernel)
        if _EXACT_SAD not in src:
            raise RuntimeError("pallas_me's SAD changed; bf16_sad cannot be planted")
        ns = dict(vars(pallas_me))
        exec(compile(src.replace(_EXACT_SAD, _ROUNDED_SAD), pallas_me.__file__,
                     "exec"), ns)
        pallas_me._me_mc_kernel = ns["_me_mc_kernel"]

    def frame(self, frame):
        if self.name != "half_batch":
            return frame
        self._n_frames += 1
        prev, self._prev_frame = self._prev_frame, frame
        return prev if self._n_frames % 2 == 0 and prev is not None else frame

    def after_submit(self, encoder) -> None:
        if self.name != "state_unchanged":
            return
        import jax.numpy as jnp

        if encoder._ref is None:
            return
        if self._ref is None:
            self._ref = tuple(jnp.array(p, copy=True) for p in encoder._ref)
        else:
            # a fresh copy each time: the encoder's step donates its inputs
            encoder._ref = tuple(jnp.array(p, copy=True) for p in self._ref)

    def outputs(self, done: list) -> list:
        if self.name != "token_altered":
            return done
        out = []
        for au, stats, meta in done:
            self._n_aus += 1
            if self._n_aus % 8 == 0 and len(au) > 64:
                b = bytearray(au)
                b[len(b) // 2] ^= 0x5A
                au = bytes(b)
            out.append((au, stats, meta))
        return out

    def credit(self, captures: list, i: int) -> int:
        """Index of the capture the check compares capture i's AU with."""
        return i - 1 if self.name == "stale" and i > 0 else i
