from .ops import group_updates_by_page
