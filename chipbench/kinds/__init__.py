"""One closed-loop driver per kind of traffic, found by `drivers.kind`."""
