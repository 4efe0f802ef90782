"""Per-defense yardstick, found by ``cfg.defense.lower()``: the ops and
bytes one call needs, and the host reference ``correct`` is decided by."""
