"""The yardstick: everything a later PR may not change sits under benchmark/."""
