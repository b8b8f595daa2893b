"""Drivers: one per kind of system path a cell drives (``run(cell, ...)``)."""
