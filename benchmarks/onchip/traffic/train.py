"""Training steps over a ring of `ring_batches` seeded batches of
`batch` images and labels, resident on the device."""

from harness import traffic

CELL = "train"
ring = traffic.train_ring
