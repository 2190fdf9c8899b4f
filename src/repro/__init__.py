"""repro — Decomposing Collectives for Exploiting Multi-lane Communication."""
