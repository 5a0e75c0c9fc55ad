"""The benchmark's general code: cell specs, device stamp, probes on the
program's layers, the closed-loop sweep and the correctness check.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` or ``bench/metrics``; nothing here names a cell.
"""
