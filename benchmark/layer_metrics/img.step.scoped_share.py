"""`step.scoped_share` under the name that moves `images_per_s`: a per-layer
metric names the one end-to-end metric it should move."""

from benchmark.harness.readers import reader

read = reader("step.scoped_share")
