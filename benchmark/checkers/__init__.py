"""Each configuration's kind of history: one module per checker, named by
the configuration's ``checker`` key, with its traffic mix, generator,
check, answer and plain reference (the contract is in harness.py)."""
