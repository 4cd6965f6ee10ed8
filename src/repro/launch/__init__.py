"""Launch layer: production mesh, dry-run driver, serve entry point."""
