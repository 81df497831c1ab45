"""The benchmark's own tests: CPU only, small sizes."""
