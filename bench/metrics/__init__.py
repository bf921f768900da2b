"""One reader per metric (``<metric>.py``), and the arithmetic they share."""
