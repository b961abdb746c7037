"""Launch layer: mesh construction, decode cache shapes, training launcher."""
