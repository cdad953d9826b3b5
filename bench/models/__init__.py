"""Model families: one module per architecture, ``models/<family>.py``,
named by a configuration file's ``"family"`` key (``spec.family``).

Every function of a family takes the configuration dict. A family gives:

  program_config(cfg)        the program's ``ModelConfig``
  make(cfg, key)             canonical weights from the seed, rounded to
                             the stated dtype (traced: call it inside jit)
  pack_unrolled(c), pack_scanned(c), unpack_unrolled(tree)
                             the program's parameter layouts of canonical
                             weights ``c``, and back
  logits(cfg, w, tokens, *, precision), loss(cfg, w, tokens, labels, *,
      precision)             the plain reference (``reference/ops.py``
                             says what each precision computes)
  n_params(cfg)              parameters held on this chip: the gradient
                             message's length, every weight a decode reads
  model_flops_per_token(cfg, seq_len)
                             forward plus backward FLOPs of one token
  kv_bytes_per_position(cfg, bytes_per_value)
  tiny(cfg)                  the configuration at a size a CPU test holds

Only ``program_config`` touches the program; the reference imports
nothing of it.
"""
