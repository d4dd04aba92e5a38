"""Per-kind counts for ``flops.py``: ``counts/<kind>.py`` for each layer
kind (the part of a ``layer_pattern`` tag before ``:``)."""
