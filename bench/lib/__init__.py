"""The cell benchmark's harness: what belongs to no single cell."""
