"""On-chip benchmark of the LCD serving engine (see bench/README.md)."""
