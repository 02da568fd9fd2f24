"""The benchmark of dryad-tpu: one command, cells found by name.

``BENCHMARK.json`` at the root of the checkout is the manifest.  Everything
that belongs to one configuration, one traffic mix, one cell, one runner or
one per-layer metric is a file of its own under this directory, found by the
name the manifest gives it; adding one never edits a file that is there.
"""
