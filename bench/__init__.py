"""Chip benchmark of the packed serving engine, driven by data.

``BENCHMARK.json`` at the repository root names the cells.  Everything
that belongs to one configuration, traffic mix or metric sits in a file
of its own under this directory, found by name:

* ``configs/<config>.json``  -- model preset, bit pair, engine shape;
* ``traffic/<traffic>.json`` -- parameters of the one traffic generator;
* ``limits/<cell>.json``     -- the limits of the cell's correctness check;
* ``e2e/<metric>.py``        -- reader of one end-to-end metric;
* ``metrics/<metric>.py``    -- reader of one per-layer metric;
* ``peaks.json``             -- published peaks, keyed by ``device_kind``.

Run one cell once with ``python bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.
"""
