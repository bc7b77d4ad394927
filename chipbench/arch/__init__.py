"""Architectures, one module per kind of model, named by the ``reference``
key of a configuration file (``harness.spec.architecture``), as its plain
reference is.  The harness asks a configuration's module, and nothing
else, for what depends on the architecture:

``arch_config(a)``
    the serving program's ``ArchConfig`` for the configuration's sizes
    ``a`` (its ``arch`` object), nested parts included.
``stacks(a)``
    the layer stacks of the program's parameter tree, ``[(name, depth)]``:
    each is one key of the tree whose leaves are stacked over its depth.
``layer_weights(key, a, stack, i)``, ``outer_weights(key, a)``
    one layer of a stack (``i`` may be traced) and everything outside the
    stacks; ``harness.weights`` makes and counts them.
``prefill_chunk(a, start, valid, last, kv_bytes)``,
``decode_token_step(a, contexts, kv_bytes)``
    ``(flops, bytes)`` of useful work of the whole model, as
    ``harness.roofline`` defines it.
``kernel_calls(a, kernel, *work, kv_bytes)``
    every call of the kernel named ``kernel`` that one step makes for the
    work described (``paged_decode``: the contexts of one token step;
    ``paged_prefill``: a chunk's start and valid rows), as
    ``[(calls, flops, bytes)]`` with the counts of one call; ``[]`` for a
    kernel the architecture does not run.

A new architecture is a new module here and, beside it, its reference
``chipbench/reference/<name>.py``; no other file changes.
"""
