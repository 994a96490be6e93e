"""What every cell shares: the manifest, the window and its trace slice,
the registry difference, the xplane reduction, the peaks, the output line."""
