"""The benchmark's own code: traffic, references, counts, trace reduction,
and the comparison that decides ``correct``. Only ``adapter.py`` imports the
program under test."""
