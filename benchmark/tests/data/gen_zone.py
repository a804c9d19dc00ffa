"""A generator a configuration names (``deploy.py``), for the tests:
``gen.py``'s deployment with one tag more, ``zone`` (``i % zones``,
``z0`` ...), at the end of every line. It builds on ``gen.py`` instead
of copying it, and its chunks are made by ``gen.generate``'s worker
processes, which find this module again by its name."""

import numpy as np

import gen


class Data(gen.Data):
    tags = gen.Data.tags + ("zone",)

    def __init__(self, spec: dict):
        super().__init__(spec)
        self.zones = int(spec["zones"])
        if not 0 < self.zones <= 10:
            raise ValueError("one digit of zone in the line template")

    def tag_ids(self, tagk, idx):
        return idx % self.zones if tagk == "zone" \
            else super().tag_ids(tagk, idx)

    def tag_name(self, tagk, i):
        return f"z{i}" if tagk == "zone" else super().tag_name(tagk, i)

    def tag_index(self, tagk, name):
        if tagk != "zone":
            return super().tag_index(tagk, name)
        return int(name[1:]) if name[:1] == "z" and name[1:].isdigit() \
            and int(name[1:]) < self.zones else -1

    def tag_count(self, tagk):
        return self.zones if tagk == "zone" else super().tag_count(tagk)


def chunk_lines(data: Data, seed: int, chunk: int):
    """``gen.chunk_lines`` with `` zone=z<n>`` before every newline."""
    text, values, points = gen.chunk_lines(data, seed, chunk)
    lines = np.frombuffer(text, dtype=np.uint8).reshape(points, -1)
    o = len(data.metric) + 1 + 25          # the host's seven digits
    host = (lines[:, o:o + 7].astype(np.int64) - 48) @ 10 ** np.arange(
        6, -1, -1)
    tail = np.frombuffer(b" zone=z0\n", dtype=np.uint8)
    out = np.empty((points, lines.shape[1] - 1 + len(tail)),
                   dtype=np.uint8)
    out[:, :lines.shape[1] - 1] = lines[:, :-1]
    out[:, lines.shape[1] - 1:] = tail
    out[:, -2] = 48 + data.tag_ids("zone", host)
    return out.tobytes(), values, points


def generate(data: Data, seed: int, on_text=None):
    return gen.generate(data, seed, on_text, lines=chunk_lines)
