"""The benchmark's yardstick: cell loading, the general traffic generator,
the window, the plain reference, the comparison and the trace reduction."""
