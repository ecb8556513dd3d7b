"""Chip benchmark of the LogHD classifier service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` on the accelerator it is started on.
Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

  configs/<config>.json   sizes, source, cuts, correctness limits, the
                          residency and a precision for each stage
  configs/<config>.py     builds the model and its request pool from the
                          seed; exports ``reference_scores`` and the
                          ``STAGES`` that take a precision
  traffic/<mix>.json      arrival parameters, read by ``loadgen``
  modes/<mode>.py         drives one measured window (named by the mix);
                          its ``delta``, if any, gives the traced slice's
                          counters
  metrics/<metric>.py     reduces a run to one metric
  peaks.json              peak rates per ``device_kind``
"""
