"""The one traffic generator: frames and their arrivals, from a mix's data.

A mix file (`traffic/<mix>.json`) holds only parameters:

  loop            "open": frames arrive on a wall-clock schedule whatever
                  the server does (cameras); "closed": the queue is kept
                  `depth` frames deep (an offline replay of recordings).
  slots           the Server's batch slots for the network.
  frames_per_trigger
                  open loop: frames released together at each trigger
                  (1: one camera; n: n synchronized cameras).
  depth           closed loop: queued frames kept ahead of the server.
  deadline        open loop: "period" — a frame is due before the next
                  trigger (the sweep's criterion for the knee).
  queue_capacity  the Server's bounded queue; a frame it refuses is lost.
  frame_pool      distinct random frames drawn from the seed.
  check_sample    served frames compared with the reference per run.

A cell's rate (`cells/<cell>.json`, key `rate_hz`, in triggers per
second) is data of the cell. Every seed gets the same schedule; the seed
changes only the pixels and the weights.
"""

from __future__ import annotations

import numpy as np


def open_loop_offsets(mix: dict, rate_hz: float, seconds: float) -> np.ndarray:
    """Due times (seconds from the window's start) of the frames of an
    open-loop window: triggers at k / rate_hz for k / rate_hz < seconds."""
    n_triggers = int(np.ceil(seconds * rate_hz - 1e-9))
    per = int(mix.get("frames_per_trigger", 1))
    return np.repeat(np.arange(n_triggers) / rate_hz, per)


class Frames:
    """Frame k of a run: pool frame k mod P with every byte XORed by
    (k div P) mod 256, so frames do not repeat within 256 * P of them."""

    def __init__(self, shape: tuple[int, ...], pool: int, seed: int):
        rng = np.random.default_rng([seed, 0x6672616D])
        self.pool = rng.integers(-128, 128, size=(pool,) + tuple(shape),
                                 dtype=np.int8)

    def __call__(self, k: int) -> np.ndarray:
        p = len(self.pool)
        return (self.pool[k % p].view(np.uint8)
                ^ np.uint8((k // p) % 256)).view(np.int8)
