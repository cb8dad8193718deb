"""Counts of operations and bytes, one file a configuration family.

A configuration names its count with ``"cost": "<name>"``; the harness
then builds ``run.model`` from ``bench/costs/<name>.py``'s
``Model.from_config(cfg)``.  The readers call ``decode(keys)`` (one
token per listed slot, each attending ``keys`` keys) and
``prefill(chunks)`` (per slot ``(start, n, emits)``), each returning
``(flops, bytes)`` of useful work; ``bench.cost.least_time`` turns them
into a least time.  Without the key the dense decoder's
``bench.cost.Model`` counts.
"""
