"""Job kinds: one module per kind, named by a traffic file's ``job``."""
