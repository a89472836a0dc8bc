"""One module per architecture, picked by the configuration's
``architectures[0]``: ``bench/arch/<Architecture>.py`` (loaded by path,
``spec.arch_module``).  A configuration of a new architecture brings its
module beside its file; no other file of the harness changes.

What a module provides:

  shapes(c)        the leaf shapes of the weights in the program's
                   parameter layout (nested dicts of tuples);
                   ``weights.make_params`` draws them from the seed
  dims(c)          a ``roofline.Dims``: head width (the configuration's
                   ``head_dim`` where it gives one, else hidden size over
                   heads), the weights a token multiplies (the experts it
                   is routed to only) and each layer's attention window
                   (None for full attention), which the FLOP and byte
                   counts read
  Reference(c, params, mode=, bits=, amax=)
                   the plain reference forward (``bench/reference.py``
                   says what ``mode`` and ``bits`` do), with
                   ``.run(tokens, length, targets)`` returning per position
                   the largest logit, the logit of ``targets``, the argmax,
                   and the K and V of every layer, stacked in the program's
                   layer order (layers, length, kv heads, head dim)

and may provide:

  calibrate(c, params, *, seq_len, seed, n_batches, batch_size)
                   the activation scales of the encoded linears (what
                   ``amax`` takes); a configuration with an ``encoded``
                   block needs it
  cache_of(engine, req, n_pages)
                   the K and V the program holds for a request in flight,
                   as ``Reference.run`` returns them; by default
                   ``program.cache_of``, the paged K/V pools of every stage
                   joined on the layer axis

A module imports nothing of the program: it is the plain reference of
its architecture, and shares ``bench/reference.py``'s arithmetic.
"""
