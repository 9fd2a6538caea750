"""Plain references, one module per model family, named by configs."""
