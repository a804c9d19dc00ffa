"""A judge a configuration names (``deploy.py``), for the tests:
``reference.py``'s and one aggregator more, ``avg``: the group's sum
over the number of its members that have a value in the bucket, real
or interpolated (OpenTSDB's ``avg`` interpolates linearly, as ``sum``
does). Everything else (``compare``, ``rows_to_grid``, ``Cells``) is
``reference.py``'s, which the loader falls back on."""

import numpy as np

import reference


class Reference(reference.Reference):
    aggregators = reference.Reference.aggregators + ("avg",)

    def _reduce(self, grid, ties, gids, g, agg, secs, rate, counter_max):
        if agg != "avg":
            return super()._reduce(grid, ties, gids, g, agg, secs, rate,
                                   counter_max)
        out = super()._reduce(grid, ties, gids, g, "sum", secs, rate,
                              counter_max)
        have = ~np.isnan(reference.lerp_fill(grid))
        n = np.stack([np.bincount(gids[have[:, j]], minlength=g)
                      for j in range(grid.shape[1])], axis=1)
        n = np.maximum(n, 1)
        out.want /= n
        out.scale /= n
        out.atol /= n
        return out
