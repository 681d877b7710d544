"""chipbench: the yardstick for tpu-sched on the chip.

One command runs one cell once (``python -m chipbench --workload <name>
--seed <n> --seconds <s> --trace <0|1>``). Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own under this directory, found by the name ``BENCHMARK.json`` gives;
see ``README.md``. Nothing here imports ``bench``, ``benchmarks``,
``chip_smoke``, ``tools`` or ``kubernetes_tpu.ops`` / ``.tensors`` /
``.streaming``: the program is driven through the surface an operator
has, and the reference (``reference.py``) imports nothing of it at all.
"""
