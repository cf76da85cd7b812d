"""The transcript model plane: a decoder-only language model (window and
full attention mixed, routed experts beside a shared one) served by a
step engine over a two-class paged cache.

- ``model.py``: configuration, the layer mathematics and the step
  program (one prefill chunk of one request beside every decoding row);
- ``moe.py``: sigmoid routing with a selection bias and the dropless
  grouped expert products;
- ``cache.py``: the page pools and the two classes of page table;
- ``engine.py``: the step engine, its step records and the process
  singleton; ``load.py``: a model directory on disk.
"""
