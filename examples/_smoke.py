"""Shared --smoke guard for the example scripts: force the CPU backend
BEFORE jax initialises so a smoke run never takes the chip (one process
at a time may hold it). Import this FIRST in every example."""
import sys

if "--smoke" in sys.argv:
    import jax
    jax.config.update("jax_platforms", "cpu")
