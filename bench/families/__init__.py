"""One module per program model family: ``families/<cfg.family>.py``.

``bench.model.load_model`` loads the module of the preset's
``cfg.family`` by path; a preset of a family with no module is refused.
Everything that depends on the block's structure lives in the module;
the harness, the weight builder, the reference's block loop and the
metric readers only call what it supplies:

* ``Dims``, ``dims(cfg) -> Dims``: the sizes the benchmark's own code
  reads (the weight builder, the reference and the counters never call
  into the program).  ``Dims`` has at least ``n_layers``, ``d_model``
  and ``vocab``.
* ``check(name, doc, cfg)``: raise ``ValueError`` where the full-size
  preset departs from the configuration file ``doc``.
* ``layer_spec(dm) -> {path: (shape, kind)}``: one layer's leaves in the
  program's layout, ``/``-separated paths.  ``kind == "proj"`` marks a
  packed projection ``[K, N]``, which the builder packs with the
  program's ``prepack_dense``; every other leaf is served as made.
* ``layer_float(seed32, dm, i) -> {path: float32}``: layer ``i``'s float
  weights, keyed like ``layer_spec``, traceable in ``seed32`` and ``i``.
* ``top_float(seed32, dm) -> {path: float32}``: the leaves outside the
  layers: ``"embed"`` ``[vocab, d_model]``, the final norm and, where
  the family has one, an untied head.
* ``ref_layer(w, x, dm, bits, rnd) -> x``: the float32 reference of one
  layer over ``x`` ``[B, T, d_model]``, from ``w = layer_float(...)``,
  ``bits = (w_bits, a_bits)`` and the control's rounding ``rnd``
  (``bench.reference.LOWP``).  It must be causal: a position reads
  nothing to its right, so right padding changes no target.
* ``ref_head(top, x, dm, rnd) -> [R, vocab]``: the final norm and LM
  head over ``x`` ``[R, d_model]``, from ``top = top_float(...)``.
* ``kernel_calls(dm, n_slots, chunk) -> [(M, K, N, calls)]``: the packed
  kernel's calls in one engine step of ``n_slots`` slots by ``chunk``
  tokens, as the step issues them.
* ``useful_ops(dm, chunks, n_sampled) -> (int_ops, float_ops)``: one
  step's useful work; ``chunks`` holds (start position, valid tokens)
  of each slot fed, ``n_sampled`` the logit rows sampled.  Padding rows
  count nothing.
"""
