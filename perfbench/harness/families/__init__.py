"""Operator graphs of architecture families that the plain reference does
not restate itself.

A configuration whose architecture has a family outside
``reference.OWN_FAMILIES`` brings ``families/<family>.py`` (``-`` read as
``_``), found by name (``spec.family``).  It defines

    layers(g, a, batch, q_len, kv_len, tp, decode) -> None

which adds to the ``reference.Graph`` ``g`` every layer of the
architecture ``a`` (its entry under ``archs`` in the configuration file)
between the embedding copy and the logits matmul, which every family
shares; ``q_len`` is 1 for a decode step.  It may reuse the reference's
graph helpers (``Graph``, ``attention``, ``ffn``, ``moe``) and writes only
what is new.  Like the reference, it imports nothing of the program under
test and takes nothing it made.
"""
