"""The on-chip benchmark's yardstick: traffic, weights, reference, clock,
trace reduction and the comparison that decides ``correct``.

Nothing here imports the program except ``serve``, which builds and drives
the system under test.
"""
