"""The benchmark's harness: see ../run.py."""
