"""The benchmark's yardstick: graph generation, traffic, references, the
trace reduction and the peaks table. Nothing here imports the program."""
