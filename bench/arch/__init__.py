"""Architecture modules: ``<arch>.py`` holds what the harness knows of one
family of models, and a configuration file names it (``"arch"``), so a
new family is added with files alone (``bench.registry.arch``). A module
exports:

* ``check_supported(cfg)``: raise ``ValueError`` for a key the program
  cannot honour (never drop one);
* ``program_config(cfg)``: the program's ``ModelConfig`` for the file;
* ``widths(cfg)``: the published widths under the module's own names, the
  ``w`` that every count below takes, and that a run carries as
  ``RunRecord.widths``;
* the counts the per-layer readers use, each a lower bound of what the step
  had to do, so that no share of a peak can pass 100% on the benchmark's
  account (only live tokens count; an expert's weights only where a call's
  rows must have reached it; attention reads each row's resident cache once
  and computes only unmasked scores; the head scores one position a row):
  ``layer_kinds(w)``, ``matmul_params(w, kind)``, ``attn_work(w, rows,
  nbytes)``, ``gmm_work(w, tokens, nbytes)``, ``model_flops(w, tokens,
  rows, attn_rows)``;
* optionally ``FAN_IN``: {parameter name: shape -> fan-in}, for leaves whose
  layout ``bench.model.make_weights``' own rule does not know.
"""
