"""One module per kind of traffic, found by the ``driver`` key of a traffic
mix: ``train`` steps a compiled training step."""
