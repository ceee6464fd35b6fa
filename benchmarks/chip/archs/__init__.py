"""The architectures the benchmark can run, one file each, found by name.

A configuration file names its architecture (``"architecture": "<a>"``);
one that names none is a ``DEFAULT``.  Two files make an architecture:

- ``archs/<a>.py`` faces the program: ``dims(conf)`` (the sizes, in a
  ``Dims`` of its own), ``program_config(conf)`` (the program's
  ``ModelConfig``), and the counts ``param_count(dims)``,
  ``weight_bytes(dims)``, ``prefill_flops(dims, prompt_len)``,
  ``decode_flops(dims, context, n_tokens)``,
  ``train_flops_per_token(dims, seq_len)`` and
  ``cache_bytes_per_token(dims)``;
- ``references/<a>.py`` is the plain float32 reference, importing nothing
  of the program: ``make_params``, ``served_gaps``, ``control_gaps``,
  ``loss_sum``, ``adamw``, ``identity`` and ``fp8``.
"""

DEFAULT = "dense_gqa"
