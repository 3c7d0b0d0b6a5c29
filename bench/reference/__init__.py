"""Plain references, one module per job kind; numpy, no program code."""
