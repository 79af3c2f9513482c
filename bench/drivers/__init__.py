"""One driver per kind of cell, found by the name in a configuration."""
