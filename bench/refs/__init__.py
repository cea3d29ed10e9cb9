"""Plain references, one module per architecture, named by a
configuration's ``reference`` key."""
