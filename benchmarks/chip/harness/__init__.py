"""The on-chip benchmark harness: loads a cell's files by name, makes
its traffic and weights from the seed, drives the system under test,
reduces the device trace, and checks the served tokens against a plain
reference."""
