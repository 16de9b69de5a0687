"""Model families, one file each, found by the ``model_type`` a
configuration file carries (``harness.load_family``). A family gives the
drivers everything they take from a model, and they take it from nowhere
else (``drivers/``, ``control.py`` and ``rehearse.py`` name no family and
read no key of a configuration):

- ``reference``: the plain reference, ``benchmark/reference/<model_type>.py``
  (``seed_key``, ``init_weights``, ``forward(..., quant)``; for training
  ``loss_and_grad``, ``vote_lion_step``, ``cosine_warmup_lr``);
- ``program_weights(key, cfg, dtype)``: the seeded weights as the program's
  tree, traceable, so that the harness makes them on the device in ONE jitted
  call (``harness.seeded_weights``);
- ``serve_model(params, cfg, dtype)``: the engine's ``ServeModel`` over them;
- for training: ``train_flags(cfg)``, the trainer's flags that name this
  model, and ``to_program`` / ``program_leaves`` / ``reference_leaf_norms`` /
  ``leaf_keys(cfg)``, the re-labelling between the reference's layout and the
  program's (no value is changed);
- ``vocab(cfg)``: how many ids the traffic draws from;
- ``reference_row_len(cell)``: the length of a reference row (all of a short
  context; for a long-context family what the cell's traffic can produce,
  not the declared positions);
- ``check_config(body)``: the family's own assertions on a configuration file;
- ``TINY``: its own tiny configuration (``model_type`` among its keys), for
  ``rehearse.py`` and the CPU tests.
"""
