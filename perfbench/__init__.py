"""On-chip benchmark of the PS³ serving path; see run.py."""
