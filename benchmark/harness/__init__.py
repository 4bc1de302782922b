"""The benchmark's harness: everything that is not one configuration's, one traffic
mix's or one per-layer metric's own."""
