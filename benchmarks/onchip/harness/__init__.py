"""The on-chip benchmark's harness: everything a cell shares."""
