"""One ``<metric>.json`` per per-layer metric (the reader module and its
arguments) and one small reader module per way of reading."""
