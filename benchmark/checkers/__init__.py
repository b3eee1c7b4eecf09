"""How each configuration's checker is driven: one module per checker,
named by the configuration's ``checker`` key."""
