"""The benchmark's own code: catalog, data, PFS stand-in, reference, checks, trace reduction."""
