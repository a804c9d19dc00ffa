"""The generator of a fleet whose collectors are not in step
(``fleet-1m-now``): ``gen.py``'s fleet, tag for tag and value for
value, with each host's points at its own second of the minute:

    ts(host, k) = t0 + phase_s(host) + k * cadence_s

``phase_s`` is drawn from the seed, uniform over ``[0, cadence_s)``,
once a host. With every series on the same second a window that moves
by a second would change its point set once in ``cadence_s`` requests,
and an answer keyed to a window rounded to the cadence would pass for
the answer of the window asked; with a phase a host, about one series
in ``cadence_s`` enters or leaves at each edge with every second "now"
moves.

The phases are set by :func:`generate` (the seed is its argument, not
the configuration's) and kept on the ``Data`` it was handed: the
workers get them with it, and the judge reads them through
``point_offset_s``, which raises before then. The values, the dropped
points and every tag are ``gen.py``'s for the same seed.
"""

import numpy as np

import gen


class Data(gen.Data):
    def __init__(self, spec: dict):
        super().__init__(spec)
        self.phase_s = None       # [series] int16, set by generate()

    def set_phases(self, seed: int) -> None:
        rng = np.random.default_rng([seed, 60 << 20])
        # int16: the workers get it with every chunk they are handed
        self.phase_s = rng.integers(0, self.cadence_s, size=self.series,
                                    dtype=np.int16)

    def point_offset_s(self, idx: np.ndarray):
        if self.phase_s is None:
            raise RuntimeError("the phases are drawn from the seed: "
                               "generate() sets them")
        return self.phase_s[idx].astype(np.int64)


def generate(data: Data, seed: int, on_text=None):
    data.set_phases(seed)
    return gen.generate(data, seed, on_text)


chunk_lines = gen.chunk_lines
