"""Batch jobs over a mesh of chips with the skew planner on: the
``distributed`` driver with the traffic's ``skew``, ``sample_fraction`` and
``hot_key_split_max`` passed into ``ShuffleOptions``.

With ``skew="auto"`` the entry point samples the emitted keys' histogram
at ``lower()`` (in set-up: the warm-up job, then the plan cache) and bakes
balanced key ranges and hot-key splits into the shuffle it compiles.
"""

from __future__ import annotations

import dataclasses

from bench.drivers import distributed


class Driver(distributed.Driver):
    @property
    def options(self):
        return self._options

    @options.setter
    def options(self, opts):
        # the distributed driver sets its options before its warm-up job:
        # they take the skew planner's fields here
        tr = self.run.traffic
        self._options = dataclasses.replace(opts, shuffle=dataclasses.replace(
            opts.shuffle, skew=tr["skew"],
            sample_fraction=float(tr["sample_fraction"]),
            hot_key_split_max=int(tr["hot_key_split_max"])))
