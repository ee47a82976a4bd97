"""Launch package: production mesh and the dry-run driver."""
