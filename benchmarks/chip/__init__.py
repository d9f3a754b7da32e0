"""The on-chip serving benchmark (``BENCHMARK.json`` at the repo root).

One command runs one cell once::

    python3 benchmarks/chip/run.py --workload qwen3b-longctx --seed 7 \\
        --seconds 10 --trace 0

Adding to it takes new files only, found by the names in
``BENCHMARK.json``; the harness is not edited:

* an architecture: ``families/<family>.py``, the module that gives the
  engine's config, the weights, the reference and the counts of one
  family of models (``families/__init__.py`` lists what it defines);
* a configuration: ``configs/<name>.json`` with the published
  ``config.json`` keys as run (and ``qkv_bias``) and the ``family`` it
  belongs to, and a ``configs`` entry naming the file, its source and
  every key changed from it;
* a traffic mix: ``traffic/<name>.json`` (keys in ``traffic.py``);
* a cell: ``cells/<workload>.json`` (the engine's ``max_batch``,
  ``max_len``, ``n_pages``, ``prefill_budget``, and the check's
  ``sample_tokens`` and ``max_logit_gap`` limit) and a ``workloads``
  entry naming the configuration, the mix and the chips (the engine's
  head-shard count);
* a metric: ``metrics/<name>.py`` defining ``read(run) -> float | None``
  over ``harness.Run`` (``None`` where the cell has nothing to read),
  and an ``end_to_end`` or ``per_layer`` entry.

``control.py`` reads the check's numbers over many seeds, with the
float8 control beside them; it is how each cell's limit was set.
"""
