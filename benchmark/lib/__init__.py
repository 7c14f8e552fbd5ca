"""The benchmark's yardstick: peaks, operation counts, traffic, trace reduction.

Nothing here imports the program under test.
"""
