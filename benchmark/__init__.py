"""The benchmark: yardstick code and data that later PRs may add to and not edit."""
