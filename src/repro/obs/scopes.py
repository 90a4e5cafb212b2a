"""Named scopes of the train step that the chip benchmark reads.

Each name is a ``jax.named_scope`` in the program; it reaches every HLO
instruction traced inside it as part of ``metadata={op_name=...}``, and so
the chip's profiler trace of the compiled step. Scopes change metadata
only, never the program the chip runs.

Forward, backward and full-remat recompute are not scopes here: JAX marks
them itself in the same op_name, as ``jvp(...)``, ``transpose(jvp(...))``
and ``.../checkpoint/rematted_computation/...``.

- ``seq_mix`` (``ssd_block_apply``, ``attention_apply``): the
  sequence-mixing core alone, the part a Pallas kernel replaces;
- ``optimizer`` (``make_train_step``): AdamW with its global-norm clip.

The ``*_vmem`` scopes of the kernels' reference bodies (``ssd_vmem``,
``flash_vmem``) sit inside ``seq_mix`` and are read by Flint's capture.
"""
SEQ_MIX = "seq_mix"
OPTIMIZER = "optimizer"
