"""The benchmark's own traffic generators; each ``<mix>.json`` here holds the
parameters of one traffic mix that they read."""
