"""The on-chip benchmark of apex_tpu: the yardstick. See README.md."""
